"""Histogram binning as a Pallas TPU kernel: the ``message_histogram``
reduction.

Samples sit on lanes: coordinates are ``[1, N]``, blocked ``(1, BE)``.
Per block the kernel floors the (pre-scaled) bin coordinate, clamps it
into ``[0, n_bins)``, builds the ``[NB, BE]`` one-hot bin matrix, and
lifts the counts onto the ``[1, NB]`` accumulator with one ``dot_general``
of a ones row against it, contracting the lane axis — the same
scatter-free one-hot matmul idiom as :mod:`repro.kernels.time_bin`.

Callers pass *bin coordinates* (sample scaled so bin ``i`` covers
``[i, i+1)``).  Feeding exact host-computed indices centered at
``idx + 0.5`` makes the in-kernel floor exact in f32 for any bin count
below 2²³ — that is how ``message_histogram`` keeps numpy-identical
counts; raw coordinates bin to f32 rounding instead.  Padding samples
carry a negative coordinate and are masked out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["hist_bin"]


def _kernel(x_ref, out_ref, *, n_bins):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]                                       # [1, BE] (<0 pad)
    be = x.shape[1]
    idx = jnp.where(x >= 0.0,
                    jnp.clip(jnp.floor(x).astype(jnp.int32), 0, n_bins - 1),
                    -1)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (n_bins, be), 0)
              == idx).astype(jnp.float32)                # [NB, BE]
    out_ref[...] += jax.lax.dot_general(
        jnp.ones((1, be), jnp.float32), onehot, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)              # [1, NB]


def hist_bin(coords, *, n_bins: int, be: int = 256, interpret: bool = True):
    """coords [N] f32 bin coordinates (<0 ignored; floor+clamp to bin id)
    → [n_bins] f32 counts."""
    n = coords.shape[0]
    nb_blocks = max(-(-n // be), 1)
    pad = nb_blocks * be - n
    coords = jnp.pad(coords.astype(jnp.float32), (0, pad),
                     constant_values=-1.0)

    out = pl.pallas_call(
        functools.partial(_kernel, n_bins=n_bins),
        grid=(nb_blocks,),
        in_specs=[pl.BlockSpec((1, be), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, n_bins), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n_bins), jnp.float32),
        interpret=interpret,
    )(coords[None, :])
    return out[0]
