"""The benchmark's own spans and counters, placed around the program's layer
boundaries from outside: nothing here edits the program.

* spans around ``repro.core.accel.canonical_order`` (every call site reaches
  it as a module attribute), around each jit wrapper of
  ``repro.kernels.ops`` that the accelerator adapters call (also reached as
  module attributes, at call time), around the service's handle pool and
  around every client request;
* the shapes of each kernel wrapper call, for the roofline count;
* JAX's own compile events (backend compiles and persistent-cache hits);
* with ``annotate``, every span is also a ``jax.profiler.TraceAnnotation``,
  so a profiler trace can say what the host did while the device was idle.

A probe whose target the program no longer has is skipped: the metric that
reads it then finds nothing and is left out.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

KERNEL_WRAPPERS = ("segment_sum_matrix", "pair_sum_matrix",
                   "histogram_counts", "time_profile_matrix")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Probes:
    def __init__(self, annotate: bool = False,
                 perturb: Optional[Callable] = None):
        self.annotate = annotate
        self.perturb = perturb
        self.spans: List[Tuple[str, int, int]] = []
        self.kernel_calls: List[Tuple[str, dict, int, int]] = []
        self.compiles: List[int] = []      # ns timestamps of backend compiles
        self.cache_hits: List[int] = []    # ... of persistent-cache loads
        self._lock = threading.Lock()
        self._undo: List[Callable[[], None]] = []

    # -- spans -------------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation("bench:" + name)
            ann.__enter__()
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            t1 = time.perf_counter_ns()
            if ann is not None:
                ann.__exit__(None, None, None)
            with self._lock:
                self.spans.append((name, t0, t1))

    def _wrap(self, owner, attr: str, make: Callable) -> None:
        orig = getattr(owner, attr, None)
        if orig is None:
            return
        setattr(owner, attr, make(orig))
        self._undo.append(lambda: setattr(owner, attr, orig))

    # -- install / remove --------------------------------------------------
    def install(self) -> None:
        import repro.core.accel as accel
        import repro.kernels.ops as kops

        def sorted_span(orig):
            def canonical_order(*a, **kw):
                with self.span("canonical_order"):
                    return orig(*a, **kw)
            return canonical_order
        self._wrap(accel, "canonical_order", sorted_span)

        for name in KERNEL_WRAPPERS:
            self._wrap(kops, name, lambda orig, name=name:
                       self._kernel_probe(name, orig))

        from jax._src import monitoring

        def on_duration(event, duration, **kw):
            if event == _COMPILE_EVENT:
                with self._lock:
                    self.compiles.append(time.perf_counter_ns())

        def on_event(event, **kw):
            if event == _CACHE_HIT_EVENT:
                with self._lock:
                    self.cache_hits.append(time.perf_counter_ns())
        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
        self._undo.append(lambda: monitoring.unregister_event_duration_listener(
            on_duration))
        self._undo.append(lambda: monitoring.unregister_event_listener(
            on_event))

    def wrap_handles(self, pool) -> None:
        """Span the service's handle lookup (open or revalidate)."""
        def make(orig):
            def get(*a, **kw):
                with self.span("handle_get"):
                    return orig(*a, **kw)
            return get
        self._wrap(pool, "get", make)

    def remove(self) -> None:
        while self._undo:
            self._undo.pop()()

    def _kernel_probe(self, name: str, orig):
        def call(*args, **kwargs):
            if self.perturb is not None:
                args, kwargs = self.perturb(name, args, kwargs, before=True)
            shapes = {"args": [tuple(getattr(a, "shape", ())) for a in args],
                      "itemsize": [int(getattr(getattr(a, "dtype", None),
                                               "itemsize", 0) or 0)
                                   for a in args],
                      "static": {k: v for k, v in kwargs.items()
                                 if isinstance(v, (int, float))}}
            with self.span("kernel:" + name):
                t0 = time.perf_counter_ns()
                out = orig(*args, **kwargs)
                t1 = time.perf_counter_ns()
            shapes["out"] = tuple(getattr(out, "shape", ()))
            shapes["out_itemsize"] = int(getattr(getattr(out, "dtype", None),
                                                 "itemsize", 0) or 0)
            with self._lock:
                self.kernel_calls.append((name, shapes, t0, t1))
            if self.perturb is not None:
                out = self.perturb(name, (out,), {}, before=False)
            return out
        return call

    # -- reading -----------------------------------------------------------
    def spans_in(self, name: str, t0: int, t1: int) -> float:
        """Seconds of ``name`` spans inside [t0, t1] (clipped)."""
        tot = 0
        for n, a, b in self.spans:
            if n == name:
                tot += max(0, min(b, t1) - max(a, t0))
        return tot / 1e9

    def count_in(self, stamps: List[int], t0: int, t1: int) -> int:
        return sum(1 for s in stamps if t0 <= s <= t1)


# ---------------------------------------------------------------------------
# perturbations: the lower-precision control and the faults a test plants
# ---------------------------------------------------------------------------

def _bf16(x):
    import jax.numpy as jnp
    if getattr(x, "dtype", None) is not None and jnp.issubdtype(
            x.dtype, jnp.floating):
        return jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32)
    return x


def control_bf16(name, args, kwargs, before):
    """Every float operand entering a kernel held in bfloat16: what float
    columns kept on the device in bfloat16 would give, and for the one-hot
    matmuls of ``seg_sum`` and ``pair_sum`` exactly what
    ``Precision.DEFAULT`` (one bfloat16 pass) gives."""
    if before:
        return tuple(_bf16(a) for a in args), kwargs
    return args[0]


def fault_alter(name, args, kwargs, before):
    """An answer altered where it is produced: one output entry of every
    kernel call scaled by 1.01 (and a count moved by one)."""
    if before:
        return args, kwargs
    out = args[0]
    import jax.numpy as jnp
    flat = jnp.ravel(out)
    bump = jnp.where(jnp.abs(flat[0]) < 100, 1.0, flat[0] * 0.01)
    return flat.at[0].add(bump.astype(flat.dtype)).reshape(out.shape)


def fault_half(name, args, kwargs, before):
    """Half of the records left out: every other record's code (or bin
    coordinate) set to the ignored value -1."""
    if not before:
        return args[0]
    import jax.numpy as jnp
    a = list(args)
    idx = 2 if name == "time_profile_matrix" else 0
    x = jnp.asarray(a[idx])
    drop = (jnp.arange(x.shape[-1]) % 2) == 1
    a[idx] = jnp.where(drop, jnp.asarray(-1, x.dtype), x)
    return tuple(a), kwargs


PERTURBATIONS: Dict[str, Callable] = {
    "control-bf16": control_bf16, "fault-alter": fault_alter,
    "fault-half": fault_half}


def kernel_bytes_ops(name: str, shapes: dict) -> Tuple[float, float]:
    """The reduction's own work for one wrapper call, from its shapes: each
    input record column read once, the output written once, one add per
    value a record adds into the output.  One-hot tiles, padding and
    blocking are not counted, so any kernel for the same reduction is read
    on the same yardstick."""
    args, item = shapes["args"], shapes["itemsize"]
    read = float(sum(int(np.prod(s)) * i for s, i in zip(args, item)))
    wrote = float(int(np.prod(shapes["out"])) * shapes["out_itemsize"])
    n = int(args[0][-1]) if args and args[0] else 0
    if name == "segment_sum_matrix":
        k = int(args[1][0]) if len(args[1]) == 2 else 1
        ops = float(n * k)
    else:
        # pair_sum: one value per record; hist_bin: one count per record;
        # time_bin: one value per overlapped bin, counted once a record
        # (records are far shorter than a bin, so few cross an edge)
        ops = float(n)
    return read + wrote, ops
