"""Weighted 2-D scatter-add as a Pallas TPU kernel: the ``comm_matrix``
sender×receiver reduction (also ``load_imbalance``'s function×rank sums).

``out[a[i], b[i]] += w[i]`` is a 2-D scatter — the TPU formulation is a
*pair of one-hot matmuls* fused into one.  Records sit on lanes: ``a``,
``b`` and ``w`` are ``[1, N]``, blocked ``(1, BE)``.  A block builds
``onehot(a)`` as ``[TA, BE]`` and ``onehot(b) * w`` as ``[TB, BE]`` and
lands the whole ``[TA, TB]`` update in one ``dot_general`` contracting the
lane axis of both (``A · Bᵀ``) at full f32 precision.

The output is tiled ``(TA, TB)`` (:func:`tile_shape`), so it may be far
larger than VMEM.  The records are bucketed by output tile first: a stable
sort on ``(a // TA, b // TB)`` (row-major) puts each tile's records
together, in their input order.
The grid is 1-D and sequential.  Scalar-prefetched per-tile maps (first
step, first record block, records ``[lo, hi)``) give each step its tile,
by a binary search over the first steps, and its record block; so a block
that two tiles share is visited once for each, and a record enters only
its own tile's matmul — the grouped-matmul pattern.  The maps are per
tile, not per step, so they stay small in SMEM however many records
come.  A tile's steps
are consecutive, so the kernel zeroes the tile on its first step and
accumulates in place; a tile with no records takes one step that takes
none and is written as zeros.  An output that fits one tile skips the
sort: a stable sort on one key is the identity, and the blocks are the
input order cut into ``BE``.

Padding records carry ``a = b = -1``; ids outside ``[0, A)`` / ``[0, B)``
are sorted past every tile (or, in one tile, equal no iota row) and
contribute nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pair_sum", "tile_shape"]

# One output tile, in f32 elements: the pipeline double-buffers the output
# block, so 2¹⁸ elements keep it within one 2 MiB tile of VMEM (the budget
# of repro.core.accel.block_size).  A tiled output takes square tiles of
# 512 × 512 (the largest power-of-two square within it).
_OUT_ELEMS = 1 << 18
_SIDE = 512


def _up(n: int, m: int) -> int:
    return -(-n // m) * m


def tile_shape(n_a: int, n_b: int) -> tuple:
    """``(TA, TB)``: the whole output when it fits one tile (rows padded
    to 8 sublanes, columns to 128 lanes), else ``_SIDE`` on each side
    that is wider."""
    if _up(n_a, 8) * _up(n_b, 128) <= _OUT_ELEMS:
        return n_a, n_b
    return min(n_a, _SIDE), min(n_b, _SIDE)


def _step(s, start, first, *, n_tiles, last):
    """Grid step ``s``'s tile, that tile's first step, and its record
    block, from the per-tile maps: the tile is the last whose first step
    is at or before ``s`` (a binary search, scalar work only), the block
    the tile's first block plus the steps since, at most ``last``.  Steps
    past the last tile's own fall to it, on blocks past its records.  One
    tile needs no maps: its steps are the blocks in order."""
    if n_tiles == 1:
        return 0, 0, s
    lo, hi = 0, n_tiles - 1
    for _ in range((n_tiles - 1).bit_length()):
        mid = (lo + hi + 1) // 2
        at = start[mid] <= s
        lo, hi = jnp.where(at, mid, lo), jnp.where(at, hi, mid - 1)
    return lo, start[lo], jnp.minimum(first[lo] + s - start[lo], last)


def _kernel(start_ref, first_ref, lo_ref, hi_ref, a_ref, b_ref, w_ref,
            out_ref, *, ta, tb, ntb, n_tiles, last):
    s = pl.program_id(0)
    t, t_first, blk = _step(s, start_ref, first_ref, n_tiles=n_tiles,
                            last=last)

    @pl.when(s == t_first)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a, b = a_ref[...], b_ref[...]                        # [1, BE] (<0 pad)
    be = a.shape[1]
    if n_tiles > 1:
        # only the tile's records [lo, hi) (a block two tiles share holds
        # the other's too); ids local to the tile
        idx = blk * be + jax.lax.broadcasted_iota(jnp.int32, (1, be), 1)
        keep = (idx >= lo_ref[t]) & (idx < hi_ref[t])
        a = jnp.where(keep, a - (t // ntb) * ta, -1)
        b = jnp.where(keep, b - (t % ntb) * tb, -1)
    oa = (jax.lax.broadcasted_iota(jnp.int32, (ta, be), 0)
          == a).astype(jnp.float32)                      # [TA, BE]
    ob = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (tb, be), 0) == b,
                   w_ref[...], 0.0)                      # [TB, BE]
    out_ref[...] += jax.lax.dot_general(
        oa, ob, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)              # [TA, TB]


def _groups(a, b, w, *, n_a, n_b, ta, tb, be):
    """The records sorted stably by tile (row-major), cut into blocks of
    ``be`` with one empty block after them; and per tile its first grid
    step, its first block and its records ``[lo, hi)``.  A tile takes a
    step for every block its records lie in (a block two tiles share is
    visited once for each), a tile with no records one step with none."""
    nta, ntb = -(-n_a // ta), -(-n_b // tb)
    n_tiles = nta * ntb
    n = a.shape[0]
    blocks = max(-(-n // be), 1)
    ok = (a >= 0) & (a < n_a) & (b >= 0) & (b < n_b)
    key = jnp.where(ok, (a // ta) * ntb + b // tb, n_tiles)
    # an argsort and gathers: a TPU compiles them far faster than a sort
    # that carries the three columns
    order = jnp.argsort(key, stable=True)
    key, a, b, w = key[order], a[order], b[order], w[order]
    bounds = jnp.searchsorted(key, jnp.arange(n_tiles + 1),
                              side="left").astype(jnp.int32)
    lo, hi = bounds[:-1], bounds[1:]
    first = lo // be
    span = jnp.maximum(hi - 1, lo) // be - first + 1
    pad = (blocks + 1) * be - n
    return ((jnp.cumsum(span) - span, first, lo, hi),
            jnp.pad(a, (0, pad), constant_values=-1),
            jnp.pad(b, (0, pad), constant_values=-1),
            jnp.pad(w, (0, pad)), blocks)


def pair_sum(a, b, w, *, n_a: int, n_b: int, be: int = 256,
             tile: tuple = None, interpret: bool = True):
    """a [N] i32 (row id, <0 ignored), b [N] i32 (col id, <0 ignored),
    w [N] f32 → [n_a, n_b] f32 with w summed at (a, b).  ``tile`` is the
    output tile ``(TA, TB)``, by default :func:`tile_shape`."""
    ta, tb = tile or tile_shape(n_a, n_b)
    nta, ntb = -(-n_a // ta), -(-n_b // tb)
    n_tiles = nta * ntb
    a = a.astype(jnp.int32)
    b = b.astype(jnp.int32)
    w = w.astype(jnp.float32)
    n = a.shape[0]
    if n_tiles == 1:
        # the input order cut into blocks, as the untiled kernel had them;
        # the maps are unused constants
        blocks = max(-(-n // be), 1)
        pad = blocks * be - n
        a = jnp.pad(a, (0, pad), constant_values=-1)
        b = jnp.pad(b, (0, pad), constant_values=-1)
        w = jnp.pad(w, (0, pad))
        maps = tuple(np.asarray([v], np.int32) for v in (0, 0, 0, n))
        steps, last = blocks, blocks - 1
    else:
        maps, a, b, w, blocks = _groups(a, b, w, n_a=n_a, n_b=n_b, ta=ta,
                                        tb=tb, be=be)
        # each tile's blocks, or one, a shared block once a tile: at most
        steps, last = blocks + n_tiles - 1, blocks

    step = functools.partial(_step, n_tiles=n_tiles, last=last)

    def row(s, start, first, lo, hi):
        return 0, step(s, start, first)[2]

    def out_tile(s, start, first, lo, hi):
        t = step(s, start, first)[0]
        return t // ntb, t % ntb

    out = pl.pallas_call(
        functools.partial(_kernel, ta=ta, tb=tb, ntb=ntb, n_tiles=n_tiles,
                          last=last),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(steps,),
            in_specs=[pl.BlockSpec((1, be), row)] * 3,
            out_specs=pl.BlockSpec((ta, tb), out_tile),
        ),
        out_shape=jax.ShapeDtypeStruct((nta * ta, ntb * tb), jnp.float32),
        interpret=interpret,
    )(*maps, a[None, :], b[None, :], w[None, :])
    return out if out.shape == (n_a, n_b) else out[:n_a, :n_b]
