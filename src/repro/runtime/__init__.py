"""The model runtime: the host tracer (no jax) and the training loop.

``Trainer``, ``TrainLoopConfig`` and ``FaultInjector`` need jax and are
imported on first access, so the analysis path can import the tracer's
spans without loading the accelerator stack."""

from .tracer import Tracer

__all__ = ["Tracer", "Trainer", "TrainLoopConfig", "FaultInjector"]


def __getattr__(name):
    if name in ("Trainer", "TrainLoopConfig", "FaultInjector"):
        from . import trainer
        return getattr(trainer, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
