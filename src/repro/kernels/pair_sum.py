"""Weighted 2-D scatter-add as a Pallas TPU kernel: the ``comm_matrix``
sender×receiver reduction (also ``load_imbalance``'s function×rank sums).

``out[a[i], b[i]] += w[i]`` is a 2-D scatter — the TPU formulation is a
*pair of one-hot matmuls* fused into one.  Records sit on lanes: ``a``,
``b`` and ``w`` are ``[1, N]``, blocked ``(1, BE)``.  A block builds
``onehot(a)`` as ``[A, BE]`` and ``onehot(b) * w`` as ``[B, BE]`` and lands
the whole ``[A, B]`` update in one ``dot_general`` contracting the lane
axis of both (``A · Bᵀ``) at full f32 precision.  Grid is 1-D over record
blocks (sequential), the output mapped to the whole ``(A, B)`` array every
step so the kernel accumulates in place.

Padding records carry ``a = b = -1``; ids outside ``[0, A)`` / ``[0, B)``
equal no iota row and contribute nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["pair_sum"]


def _kernel(a_ref, b_ref, w_ref, out_ref, *, n_a, n_b):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...]                                       # [1, BE] (<0 pad)
    b = b_ref[...]
    be = a.shape[1]
    oa = (jax.lax.broadcasted_iota(jnp.int32, (n_a, be), 0)
          == a).astype(jnp.float32)                      # [A, BE]
    ob = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (n_b, be), 0) == b,
                   w_ref[...], 0.0)                      # [B, BE]
    out_ref[...] += jax.lax.dot_general(
        oa, ob, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)              # [A, B]


def pair_sum(a, b, w, *, n_a: int, n_b: int, be: int = 256,
             interpret: bool = True):
    """a [N] i32 (row id, <0 ignored), b [N] i32 (col id, <0 ignored),
    w [N] f32 → [n_a, n_b] f32 with w summed at (a, b)."""
    n = a.shape[0]
    nb_blocks = max(-(-n // be), 1)
    pad = nb_blocks * be - n
    a = jnp.pad(a.astype(jnp.int32), (0, pad), constant_values=-1)
    b = jnp.pad(b.astype(jnp.int32), (0, pad), constant_values=-1)
    w = jnp.pad(w.astype(jnp.float32), (0, pad))

    row = pl.BlockSpec((1, be), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_kernel, n_a=n_a, n_b=n_b),
        grid=(nb_blocks,),
        in_specs=[row, row, row],
        out_specs=pl.BlockSpec((n_a, n_b), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_a, n_b), jnp.float32),
        interpret=interpret,
    )(a[None, :], b[None, :], w[None, :])
