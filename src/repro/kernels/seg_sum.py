"""Segment sum as a Pallas TPU kernel: the ``flat_profile`` reduction.

The hot loop of every per-name aggregate (flat profiles, per-rank busy
sums) is ``out[code[i]] += value[i]`` — a scatter-add, which TPUs hate.
Like :mod:`repro.kernels.time_bin`, the adaptation is a *one-hot matmul*.

Records sit on lanes: codes are ``[1, N]`` and values ``[K, N]``, blocked
``(1, BE)`` and ``(K, BE)``.  A block builds its ``[S, BE]`` one-hot from
``iota(S, BE) == code`` (a sublane broadcast of the code row) and lifts
the values onto the ``[S, K]`` accumulator with one ``dot_general`` that
contracts the lane axis of both operands (``onehot · valuesᵀ``) at full
f32 precision — the one-hot is exact in bf16, nanosecond values are not.

Grid is 1-D over record blocks (sequential); the output block is the whole
``(S, K)`` array every step, so the kernel accumulates in place.  Padding
records carry code ``-1``, which no iota row equals.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["seg_sum"]


def _kernel(code_ref, val_ref, out_ref, *, n_seg):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    c = code_ref[...]                                    # [1, BE] (<0 pad)
    be = c.shape[1]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (n_seg, be), 0)
              == c).astype(jnp.float32)                  # [S, BE]
    out_ref[...] += jax.lax.dot_general(
        onehot, val_ref[...], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)              # [S, K]


def seg_sum(code, values, *, n_seg: int, be: int = 256,
            interpret: bool = True):
    """code [N] i32 (segment id per record, <0 ignored), values [K, N] f32
    → [n_seg, K] f32 per-segment sums of each value row."""
    k, n = values.shape
    nb_blocks = max(-(-n // be), 1)
    pad = nb_blocks * be - n
    code = jnp.pad(code.astype(jnp.int32), (0, pad), constant_values=-1)
    values = jnp.pad(values.astype(jnp.float32), ((0, 0), (0, pad)))

    return pl.pallas_call(
        functools.partial(_kernel, n_seg=n_seg),
        grid=(nb_blocks,),
        in_specs=[
            pl.BlockSpec((1, be), lambda i: (0, i)),
            pl.BlockSpec((k, be), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n_seg, k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_seg, k), jnp.float32),
        interpret=interpret,
    )(code[None, :], values)
