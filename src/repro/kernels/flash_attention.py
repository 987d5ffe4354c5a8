"""Blocked (flash) attention as a Pallas TPU kernel.

Canonical TPU formulation: grid ``(BH, nq, nk)`` with the KV dimension
*arbitrary* (sequential) and online-softmax state carried in VMEM scratch
across KV steps.  Block sizes are MXU-aligned (multiples of 128 on the
lane dim; ``bq``/``bk`` default 128/256).  VMEM working set per step:

    q (bq×D) + k (bk×D) + v (bk×D) + acc (bq×D) + m,l (bq)  ≈ 4·bq·D f32

which for bq=bk=256, D=128 is ≈0.9 MB — far under the ~16 MB/core budget,
leaving room for the compiler to double-buffer the HBM→VMEM streams.

Causal + sliding-window masking happens on global row/col indices, so one
kernel serves full, local (gemma3), and prefix (hymba meta) attention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *, scale,
            causal, window, prefix_len, bq, bk, nk, sk_real):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0].astype(jnp.float32) * scale            # [bq, D]
    k = k_ref[0].astype(jnp.float32)                    # [bk, D]
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bq, bk]
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    allow = cols < sk_real
    d = rows - cols
    if causal:
        allow &= d >= 0
    if window is not None:
        win_ok = d < window
        if prefix_len:
            win_ok |= (cols < prefix_len) & (d >= 0)
        allow &= win_ok
    s = jnp.where(allow, s, _NEG)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + p.sum(axis=1)
    acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_sc[...] = m_new

    @pl.when(ki == nk - 1)
    def _fin():
        o_ref[0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=None, prefix_len=0,
                    scale=None, bq: int = 128, bk: int = 256,
                    interpret: bool = True):
    """q [BH, Sq, D]; k/v [BH, Sk, D] (GQA pre-broadcast in ops.py)."""
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    scale = scale or D ** -0.5
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    nq = -(-Sq // bq)
    nk = -(-Sk // bk)
    qpad, kpad = nq * bq - Sq, nk * bk - Sk
    if qpad:
        q = jnp.pad(q, ((0, 0), (0, qpad), (0, 0)))
    if kpad:
        k = jnp.pad(k, ((0, 0), (0, kpad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, kpad), (0, 0)))

    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             window=window, prefix_len=prefix_len,
                             bq=bq, bk=bk, nk=nk, sk_real=Sk)
    out = pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, nq * bq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :Sq]
