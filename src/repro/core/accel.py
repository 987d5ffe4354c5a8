"""NumPy-facing adapters over the Pallas reduction kernels, plus the
record set and canonical record ordering every accelerator backend shares.

The op backends registered as ``backend="pallas"`` (flat_profile,
comm_matrix, message_histogram, load_imbalance, stragglers, time_profile)
all reduce a flat *record set* — completed calls or send instants — with
f32 kernel arithmetic.  f32 sums are order-dependent, and the eager,
streaming, parallel and pack paths naturally discover records in different
orders; the digest-identity contract (same backend → byte-identical result
on every path) therefore hinges on one rule:

    **every path sorts its records into the same canonical order and
    invokes the kernel exactly once, at finalize.**

Every path holds its records in a :class:`Records`, whose
:meth:`Records.ordered` is the one caller of :func:`canonical_order`.
That order's keys are path-independent: timestamps, process ids,
*alphabetical* name positions (never raw category or interner codes,
which differ between the eager code space and the streaming first-seen
code space), and the record's own value as the final tiebreak.
:func:`canonical_order` finds that order in one sort on ``start``, then a
lexsort of the tied groups only (records whose start equals another's):
the same five-key order as a full ``np.lexsort``, so the contract does not
change.  See docs/kernels.md for the full precision contract.

This module is numpy-in / numpy-out — jax is imported lazily inside the
kernel calls so merely importing the core never pulls the accelerator
stack.  The sort is timed as the ``accel.sort`` span, and each adapter
call (operand conversion, upload, dispatch, wait and fetch) as an
``accel.kernel`` span; the ``accel.h2d_bytes`` counter adds up the
bytes its host operands take on the device, ``accel.pair_tiles`` the
output tiles each ``pair_sum`` call visited, and ``accel.sort_tied`` the
records the sort's tie pass took.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from .constants import ENTER, ET, MATCH, NAME, PROC, TS
from ..runtime import tracer

__all__ = ["Records", "matched_calls", "canonical_order", "alpha_positions",
           "block_size", "seg_sum", "pair_sum", "hist_counts",
           "count_upload"]


# One-hot tile bound, in f32 elements: a block builds [rows, BE] one-hot and
# overlap tiles, and Mosaic both holds them in VMEM (16 MiB scoped on a
# v5e) and unrolls its vector code over them, so compile time and code
# size grow with rows·BE.  2¹⁹ elements is a 2 MiB f32 tile.
_TILE_ELEMS = 1 << 19


def block_size(n: int, rows: int) -> int:
    """Deterministic record-block size (lanes) for the record kernels.

    ``rows`` is the number of one-hot rows a block builds per record (the
    output widths, e.g. ``n_seg``, or ``TA + TB`` of one pair_sum tile).
    The block is 256 lanes for small inputs, doubled until the sequential
    grid stays under ~512 steps, and capped so that ``rows × BE`` (rows
    padded to the 8-sublane tile) stays within one 2 MiB f32 tile — never
    below the 128-lane width, never above 32768.  A pure function of the
    record count and the output widths: every execution path holding the
    same record multiset for the same op picks the same partitioning,
    which keeps f32 block sums — and therefore result digests —
    path-identical."""
    rows8 = -(-max(int(rows), 1) // 8) * 8
    cap = max(_TILE_ELEMS // rows8, 128)
    cap = min(1 << (cap.bit_length() - 1), 32768)
    be = min(256, cap)
    while n > be * 512 and be < cap:
        be *= 2
    return be


def alpha_positions(names) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(sorted names, gather order, code→alphabetical-position map) for a
    code-aligned name table — the code-space-independent axis every pallas
    backend keys on.  ``arr[order]`` re-orders a code-indexed axis
    alphabetically; ``inv[code]`` is a code's alphabetical position."""
    names = np.asarray(list(names), dtype=object).astype(str)
    order = np.argsort(names, kind="stable")
    inv = np.empty(len(names), np.int64)
    inv[order] = np.arange(len(names))
    return names[order], order, inv


def canonical_order(start, end, proc, code, value) -> np.ndarray:
    """The shared sort of every accelerator backend: primary key ``start``,
    then ``end``, ``proc``, ``code`` (alphabetical name position — pass
    ``inv[raw_code]``), and ``value`` as the final tiebreak.  Records equal
    on *all* keys are interchangeable, so any two paths that hold the same
    record multiset feed the kernel bit-identical blocks.

    The order is ``np.lexsort``'s stable five-key one, found in one sort on
    ``start`` and a lexsort of only the records whose start ties with
    another's (NaNs tie with each other, -0.0 with 0.0); the input index is
    its last key.  The ``accel.sort_tied`` counter adds how many records
    that tie pass took."""
    with tracer.span("accel.sort"):
        start = np.asarray(start, np.float64)
        o = np.argsort(start)
        s = start[o]
        # new[i]: s[i] begins a tie group; NaNs, sorted last, are one group
        new = np.ones(len(s), bool)
        np.not_equal(s[1:], s[:-1], out=new[1:])
        new[np.searchsorted(s, np.nan) + 1:] = False
        tied = ~new
        tied[:-1] |= ~new[1:]
        pos = np.flatnonzero(tied)
        tracer.counter("accel.sort_tied", len(pos))
        if len(pos):
            sub = o[pos]
            o[pos] = sub[np.lexsort((
                sub, np.asarray(value, np.float64)[sub],
                np.asarray(code, np.int64)[sub],
                np.asarray(proc, np.int64)[sub],
                np.asarray(end, np.float64)[sub], np.cumsum(new[pos])))]
        return o


_COLUMNS = ("start", "end", "proc", "code", "value")


class Records:
    """One accelerated op's records, gathered in parts: a call is (enter
    ts, leave ts, process, name code, metric), a send (ts, ts, sender,
    receiver, bytes).  ``value`` is ``[N]``, or ``[N, width]`` for several
    metrics; ``names`` says whether ``code`` is a name code, which a
    cross-worker merge remaps.  Plain arrays only, so it pickles."""

    def __init__(self, names: bool = True, width: Optional[int] = None):
        self.names = names
        self.width = width
        self._parts: List[tuple] = []

    def add(self, start, end, proc, code, value) -> None:
        """Buffer one part's columns as given, uncopied."""
        self._parts.append((start, end, proc, code, value))

    def merge(self, other: "Records", code_map: np.ndarray) -> None:
        """Take a worker's parts, name codes mapped through ``code_map``."""
        self._parts += [(s, e, p, code_map[c] if self.names else c, v)
                        for s, e, p, c, v in other._parts]

    def columns(self) -> tuple:
        """The five columns concatenated across parts, kept as one part."""
        if len(self._parts) != 1:
            if self._parts:
                cols = tuple(np.concatenate(c) for c in zip(*self._parts))
            else:
                idx, ts = np.zeros(0, np.int64), np.zeros(0)
                cols = (ts, ts, idx, idx, np.zeros(
                    (0, self.width) if self.width is not None else 0))
            self._parts = [cols]
        return self._parts[0]

    def ordered(self, *which: str, inv: Optional[np.ndarray] = None):
        """The ``which`` columns in :func:`canonical_order`, each code first
        replaced by ``inv[code]`` (for name codes, the alphabetical
        positions of :func:`alpha_positions`)."""
        cols = dict(zip(_COLUMNS, self.columns()))
        if inv is not None:
            cols["code"] = inv[cols["code"]]
        value = cols["value"]
        o = canonical_order(cols["start"], cols["end"], cols["proc"],
                            cols["code"],
                            value if value.ndim == 1 else value[:, 0])
        return tuple(cols[k][o] for k in which)


def matched_calls(ev, metrics, keep: Optional[np.ndarray] = None
                  ) -> Records:
    """The matched calls (Enter rows with a Leave, and ``keep``) of an
    event frame, NaN-free ``value`` ``[N]`` for one metric name or
    ``[N, K]`` for a sequence of them."""
    match = np.asarray(ev.column(MATCH), np.int64)
    sel = ev.cat(ET).mask_eq(ENTER) & (match >= 0)
    sel = np.nonzero(sel if keep is None else sel & keep)[0]
    ts = np.asarray(ev[TS], np.float64)
    one = isinstance(metrics, str)
    vals = [np.nan_to_num(np.asarray(ev.column(m), np.float64)[sel])
            for m in ([metrics] if one else metrics)]
    recs = Records(width=None if one else len(vals))
    recs.add(ts[sel], ts[match[sel]], np.asarray(ev[PROC], np.int64)[sel],
             ev.codes(NAME)[sel], vals[0] if one else np.stack(vals, axis=1))
    return recs


def count_upload(*operands) -> None:
    """Add the bytes that one kernel call's host operands take on the
    device (in the dtypes jax gives them) to ``accel.h2d_bytes``."""
    import jax
    tracer.counter("accel.h2d_bytes", sum(
        int(np.size(a)) * np.dtype(
            jax.dtypes.canonicalize_dtype(np.asarray(a).dtype)).itemsize
        for a in operands))


def seg_sum(code: np.ndarray, values: np.ndarray, n_seg: int) -> np.ndarray:
    """Per-segment column sums on the accelerator: code [N] (<0 ignored),
    values [N] or [N, K] → float64 [n_seg] / [n_seg, K] (f32 kernel
    arithmetic, widened on the way out)."""
    import jax.numpy as jnp

    from ..kernels.ops import segment_sum_matrix
    values = np.asarray(values, np.float64)
    squeeze = values.ndim == 1
    if squeeze:
        values = values[:, None]
    if n_seg <= 0 or values.shape[1] == 0:
        out = np.zeros((max(n_seg, 0), values.shape[1]))
        return out[:, 0] if squeeze else out
    with tracer.span("accel.kernel", kernel="seg_sum"):
        # lane-major: the kernel takes one value row per metric
        ops = (np.asarray(code, np.int32),
               np.ascontiguousarray(values.T, np.float32))
        out = np.asarray(segment_sum_matrix(
            *(jnp.asarray(a) for a in ops), n_seg=int(n_seg),
            be=block_size(len(values), n_seg)), np.float64)
    count_upload(*ops)
    return out[:, 0] if squeeze else out


def pair_sum(a: np.ndarray, b: np.ndarray, w: np.ndarray, n_a: int,
             n_b: int) -> np.ndarray:
    """Weighted 2-D scatter-add on the accelerator: a, b [N] (<0 ignored),
    w [N] → float64 [n_a, n_b].  The record block is sized by the rows of
    one output tile (``repro.kernels.pair_sum.tile_shape``), and the
    ``accel.pair_tiles`` counter adds the tiles the call visited."""
    if n_a <= 0 or n_b <= 0:
        return np.zeros((max(n_a, 0), max(n_b, 0)))
    import jax.numpy as jnp

    from ..kernels.ops import pair_sum_matrix
    from ..kernels.pair_sum import tile_shape
    ta, tb = tile_shape(int(n_a), int(n_b))
    with tracer.span("accel.kernel", kernel="pair_sum"):
        ops = (np.asarray(a, np.int32), np.asarray(b, np.int32),
               np.asarray(w, np.float32))
        out = np.asarray(pair_sum_matrix(
            *(jnp.asarray(x) for x in ops), n_a=int(n_a), n_b=int(n_b),
            be=block_size(len(ops[0]), ta + tb)), np.float64)
    count_upload(*ops)
    # every output tile is visited once, with records or not
    tracer.counter("accel.pair_tiles", -(-n_a // ta) * -(-n_b // tb))
    return out


def hist_counts(idx: np.ndarray, n_bins: int) -> np.ndarray:
    """Exact histogram counts on the accelerator: host-computed bin indices
    go in centered at ``idx + 0.5`` (f32-exact below 2²³), the in-kernel
    floor recovers them exactly, so the int64 counts match
    ``np.histogram`` bit for bit."""
    if n_bins <= 0:
        return np.zeros(max(n_bins, 0), np.int64)
    import jax.numpy as jnp

    from ..kernels.ops import histogram_counts
    with tracer.span("accel.kernel", kernel="hist_bin"):
        coords = np.asarray(idx, np.float64) + 0.5
        out = np.asarray(histogram_counts(
            jnp.asarray(coords, jnp.float32), n_bins=int(n_bins),
            be=block_size(len(coords), n_bins)))
    count_upload(coords)
    return np.rint(out).astype(np.int64)
