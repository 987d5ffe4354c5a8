"""Pure-jnp oracles for every Pallas kernel (the tests' ground truth)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models.attention import reference_attention

__all__ = ["flash_attention_ref", "pair_sum_ref", "time_bin_ref",
           "topk_gating_ref"]


def flash_attention_ref(q, k, v, *, causal=True, window=None, prefix_len=0,
                        scale=None):
    """q/k/v [BH, S, D] — wraps the model oracle (adds/removes head axis)."""
    out = reference_attention(q[:, :, None, :], k[:, :, None, :],
                              v[:, :, None, :], causal=causal, window=window,
                              scale=scale) if prefix_len == 0 else \
        _prefix_ref(q, k, v, causal, window, prefix_len, scale)
    return out[:, :, 0, :] if prefix_len == 0 else out


def _prefix_ref(q, k, v, causal, window, prefix_len, scale):
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    scale = scale or D ** -0.5
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qpos = jnp.arange(Sq)
    kpos = jnp.arange(Sk)
    d = qpos[:, None] - kpos[None, :]
    m = jnp.ones((Sq, Sk), bool)
    if causal:
        m &= d >= 0
    if window is not None:
        win = d < window
        win |= (kpos[None, :] < prefix_len) & (d >= 0)
        m &= win
    s = jnp.where(m[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def time_bin_ref(start, end, func, *, n_funcs, n_bins, t0, t1):
    edges = jnp.linspace(t0, t1, n_bins + 1)
    ov = (jnp.minimum(end[:, None], edges[None, 1:])
          - jnp.maximum(start[:, None], edges[None, :-1]))
    ov = jnp.maximum(ov, 0.0)
    ov = jnp.where((func >= 0)[:, None], ov, 0.0)
    onehot = jax.nn.one_hot(jnp.maximum(func, 0), n_funcs, dtype=jnp.float32)
    return onehot.T @ ov


def pair_sum_ref(a, b, w, *, n_a, n_b):
    ok = (a >= 0) & (a < n_a) & (b >= 0) & (b < n_b)
    return jnp.zeros((n_a, n_b), jnp.float32).at[
        jnp.where(ok, a, 0), jnp.where(ok, b, 0)].add(jnp.where(ok, w, 0.0))


def topk_gating_ref(logits, k):
    vals, idx = jax.lax.top_k(logits.astype(jnp.float32), k)
    return idx.astype(jnp.int32), jax.nn.softmax(vals, axis=-1)
