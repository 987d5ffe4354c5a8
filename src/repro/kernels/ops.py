"""jit'd public wrappers around the Pallas kernels.

The kernels compile for the chip when JAX's default backend is a TPU and
run in Pallas interpret mode on any other backend.  The choice is made
when a wrapper is first traced (:func:`_interpret`), never at import.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention as _flash
from .hist_bin import hist_bin as _hist_bin
from .pair_sum import pair_sum as _pair_sum
from .seg_sum import seg_sum as _seg_sum
from .time_bin import time_bin as _time_bin
from .topk_gating import topk_gating as _topk

__all__ = ["flash_attention_gqa", "time_profile_matrix", "router_topk",
           "segment_sum_matrix", "pair_sum_matrix", "histogram_counts"]


def _interpret() -> bool:
    """Interpret mode everywhere but on a TPU, which always compiles."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "prefix_len",
                                             "bq", "bk"))
def flash_attention_gqa(q, k, v, *, causal=True, window=None, prefix_len=0,
                        bq=128, bk=256):
    """GQA layout [B,S,H,D] / [B,S,KVH,D] → [B,S,H,D] via the flash kernel.

    KV heads are broadcast to the query-head count before the kernel (the
    kernel operates on a flat batch×head axis)."""
    B, S, H, D = q.shape
    KVH = k.shape[2]
    G = H // KVH
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, -1, D)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, -1, D)
    out = _flash(qf, kf, vf, causal=causal, window=window,
                 prefix_len=prefix_len, bq=bq, bk=bk, interpret=_interpret())
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("n_funcs", "n_bins", "t0", "t1",
                                             "be"))
def time_profile_matrix(start, end, func, rate=None, *, n_funcs, n_bins,
                        t0, t1, be=256):
    return _time_bin(start, end, func, rate, n_funcs=n_funcs, n_bins=n_bins,
                     t0=t0, t1=t1, be=be, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("k",))
def router_topk(logits, k: int):
    return _topk(logits, k, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("n_seg", "be"))
def segment_sum_matrix(code, values, *, n_seg, be=256):
    """code [N] (<0 ignored), values [K, N] → [n_seg, K] f32 segment sums
    (repro.kernels.seg_sum) — flat_profile / per-rank busy-sum backend."""
    return _seg_sum(code, values, n_seg=n_seg, be=be, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("n_a", "n_b", "be"))
def pair_sum_matrix(a, b, w, *, n_a, n_b, be=256):
    """a, b [N] (<0 ignored), w [N] → [n_a, n_b] f32 weighted 2-D
    scatter-add (repro.kernels.pair_sum) — comm_matrix backend."""
    return _pair_sum(a, b, w, n_a=n_a, n_b=n_b, be=be, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("n_bins", "be"))
def histogram_counts(coords, *, n_bins, be=256):
    """coords [N] f32 bin coordinates (<0 ignored) → [n_bins] f32 counts
    (repro.kernels.hist_bin) — message_histogram backend."""
    return _hist_bin(coords, n_bins=n_bins, be=be, interpret=_interpret())
