"""Pipit's ``time_profile`` overlap histogram as a Pallas TPU kernel.

The paper's hottest analysis loop (§IV-B): for every function call (start,
end, func) and every time bin, accumulate the overlap length into a
``[functions × bins]`` matrix.  The TPU adaptation replaces the pandas
groupby with a *one-hot matmul*.  Events sit on lanes: start, end, func
and rate are ``[1, N]``, blocked ``(1, BE)``.  A block computes its
``[NB, BE]`` rate-weighted overlap matrix against bin edges built from an
int32 iota, then lifts it to ``[F, NB]`` via ``onehot(func) · overlapᵀ``
— one ``dot_general`` contracting the lane axis of both, at full f32
precision.

Grid is 1-D over event blocks (sequential), with the output block the
whole ``(F, NB)`` array every step so the kernel accumulates in place.
Padding events carry func ``-1`` and rate 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["time_bin"]


def _kernel(start_ref, end_ref, func_ref, rate_ref, out_ref, *, n_funcs,
            n_bins, t0, bin_w):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    f = func_ref[...]                                   # [1, BE] (<0 pad)
    be = f.shape[1]
    lo = t0 + bin_w * jax.lax.broadcasted_iota(
        jnp.int32, (n_bins, be), 0).astype(jnp.float32)  # [NB, BE]
    ov = jnp.maximum(jnp.minimum(end_ref[...], lo + bin_w)
                     - jnp.maximum(start_ref[...], lo), 0.0)
    ov = jnp.where(f >= 0, ov * rate_ref[...], 0.0)      # [NB, BE]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (n_funcs, be), 0)
              == f).astype(jnp.float32)                  # [F, BE]
    out_ref[...] += jax.lax.dot_general(
        onehot, ov, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)              # [F, NB]


def time_bin(start, end, func, rate=None, *, n_funcs: int, n_bins: int,
             t0: float, t1: float, be: int = 256, interpret: bool = True):
    """start/end [N] f32, func [N] i32, rate [N] (weight/sec; default 1)
    → [n_funcs, n_bins] f32 rate-weighted overlap."""
    n = start.shape[0]
    if rate is None:
        rate = jnp.ones_like(start)
    nb_blocks = max(-(-n // be), 1)
    pad = nb_blocks * be - n

    def row(x, dtype, fill=0):
        return jnp.pad(x.astype(dtype), (0, pad), constant_values=fill)[None]

    kern = functools.partial(_kernel, n_funcs=n_funcs, n_bins=n_bins,
                             t0=t0, bin_w=(t1 - t0) / n_bins)
    spec = pl.BlockSpec((1, be), lambda i: (0, i))
    return pl.pallas_call(
        kern,
        grid=(nb_blocks,),
        in_specs=[spec, spec, spec, spec],
        out_specs=pl.BlockSpec((n_funcs, n_bins), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_funcs, n_bins), jnp.float32),
        interpret=interpret,
    )(row(start, jnp.float32), row(end, jnp.float32),
      row(func, jnp.int32, -1), row(rate, jnp.float32))
