"""Communication analysis operations (paper §IV-C)."""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import numpy as np

from . import accel
from .constants import (DEFAULT_COMM_PREFIXES, ENTER, ET, MPI_SEND, MSG_SIZE,
                        NAME, PARTNER, PROC, TS)
from .frame import EventFrame
from .intervals import merge_intervals
from .registry import (get_backend, register_backend, register_op,
                       register_streaming)
from .streaming import StreamAgg, grow_to, stream_backend

__all__ = [
    "comm_matrix", "message_histogram", "comm_by_process", "comm_over_time",
    "comm_comp_breakdown", "comm_name_mask",
]


def _sends(trace) -> EventFrame:
    ev = trace.events
    if PARTNER not in ev:
        return EventFrame({TS: np.asarray([], np.int64)})
    return ev.mask(ev.cat(NAME).mask_eq(MPI_SEND))


@register_op("comm_matrix", needs_messages=True)
def comm_matrix(trace, output: str = "size",
                backend: str = "numpy") -> np.ndarray:
    """Process-to-process communication matrix (§IV-C, Fig. 3).

    Aggregates every send instant by (sender, receiver).

    Args:
        output: ``"size"`` (default) sums message bytes; ``"count"`` (any
            other value) counts messages.
        backend: ``"numpy"`` (default, exact) or ``"pallas"`` (pair_sum
            one-hot matmul kernel, f32 rounding; see docs/kernels.md).

    Returns:
        ``(nprocs, nprocs)`` float array; ``M[i, j]`` is the bytes (or
        number of messages) process i sent to process j.  All zeros when
        the trace records no messages.
    """
    return get_backend("comm_matrix", backend)(trace, output=output)


@register_backend("comm_matrix", "numpy")
def _comm_matrix_numpy(trace, *, output: str = "size") -> np.ndarray:
    """The exact reference: one scatter-add over the send instants."""
    s = _sends(trace)
    n = trace.num_processes
    mat = np.zeros((n, n))
    if len(s) == 0:
        return mat
    src = np.asarray(s[PROC], np.int64)
    dst = np.asarray(s[PARTNER], np.int64)
    w = np.asarray(s[MSG_SIZE], np.float64) if output == "size" else np.ones(len(s))
    np.add.at(mat, (src, dst), np.nan_to_num(w))
    return mat


def _comm_from_records(recs, n: int, op: str) -> np.ndarray:
    """The pallas comm matrix of every path: the send records through
    pair_sum, in canonical order.  Negative partner ids wrap like numpy
    fancy indexing (``-1`` is the last process); out-of-range ids raise
    the same IndexError the ``np.add.at`` reference raises instead of
    silently dropping."""
    _, _, src, dst, _ = recs.columns()
    if n == 0 or not len(src):
        return np.zeros((n, n))
    if (int(src.max()) >= n or int(dst.max()) >= n
            or int(src.min()) < 0 or int(dst.min()) < -n):
        raise IndexError(
            f"{op}: message endpoints outside the selected trace's "
            f"0..{n - 1} process range (same selection fails on "
            f"backend='numpy' too)")
    src, dst, w = recs.ordered("proc", "code", "value", inv=np.arange(n))
    return accel.pair_sum(src, dst, w, n, n)


@register_backend("comm_matrix", "pallas")
def _comm_matrix_pallas(trace, *, output: str = "size") -> np.ndarray:
    """Accelerator comm matrix: canonical-ordered send records through the
    pair_sum one-hot-matmul kernel (f32 rounding; counts exact)."""
    s = _sends(trace)
    recs = accel.Records(names=False)
    if len(s):
        ts = np.asarray(s[TS], np.float64)
        recs.add(ts, ts, np.asarray(s[PROC], np.int64),
                 np.asarray(s[PARTNER], np.int64),
                 np.nan_to_num(np.asarray(s[MSG_SIZE], np.float64))
                 if output == "size" else np.ones(len(s)))
    return _comm_from_records(recs, trace.num_processes,
                              "comm_matrix backend='pallas'")


@register_op("message_histogram")
def message_histogram(trace, bins: int = 10,
                      backend: str = "numpy") -> Tuple[np.ndarray, np.ndarray]:
    """Distribution of message sizes (§IV-C, Fig. 4).

    Args:
        bins: number of equal-width size bins over [min, max] bytes.
        backend: ``"numpy"`` (default) or ``"pallas"`` (one-hot matmul
            binning kernel).  Bin indices are computed host-side with exact
            ``np.histogram`` semantics, so both backends return *identical*
            counts (see docs/kernels.md).

    Returns:
        ``(counts, edges)`` à la ``np.histogram``: ``counts`` has ``bins``
        message counts, ``edges`` has ``bins + 1`` byte boundaries.
    """
    return get_backend("message_histogram", backend)(trace, bins=bins)


@register_backend("message_histogram", "numpy")
def _message_histogram_numpy(trace, *, bins: int = 10
                             ) -> Tuple[np.ndarray, np.ndarray]:
    s = _sends(trace)
    if len(s) == 0:
        return np.zeros(bins, np.int64), np.linspace(0, 1, bins + 1)
    sizes = np.nan_to_num(np.asarray(s[MSG_SIZE], np.float64))
    return np.histogram(sizes, bins=bins)


def _hist_indices(sizes: np.ndarray, edges: np.ndarray,
                  bins: int) -> np.ndarray:
    """Exact ``np.histogram`` bin assignment: half-open bins with the last
    bin closed — ``searchsorted(side="right") - 1`` over the edge array,
    clipped so the top edge lands in the final bin."""
    return np.clip(np.searchsorted(edges, sizes, side="right") - 1,
                   0, bins - 1)


@register_backend("message_histogram", "pallas")
def _message_histogram_pallas(trace, *, bins: int = 10
                              ) -> Tuple[np.ndarray, np.ndarray]:
    """Accelerator size histogram: exact host-side bin indices go through
    the hist_bin one-hot counting kernel — counts match numpy bit for
    bit (integer counts are exact in f32 below 2²⁴ per bin)."""
    s = _sends(trace)
    if len(s) == 0:
        return np.zeros(bins, np.int64), np.linspace(0, 1, bins + 1)
    sizes = np.nan_to_num(np.asarray(s[MSG_SIZE], np.float64))
    edges = np.histogram_bin_edges(sizes, bins=bins)
    return accel.hist_counts(_hist_indices(sizes, edges, bins), bins), edges


@register_op("comm_by_process")
def comm_by_process(trace, output: str = "size") -> EventFrame:
    """Total communication volume per process (§IV-C).

    Args:
        output: ``"size"`` (default) sums bytes; anything else counts
            messages.

    Returns:
        EventFrame with one row per process: ``Process``, ``sent``,
        ``received``, and ``total`` (sent + received), in bytes or message
        counts.
    """
    s = _sends(trace)
    n = trace.num_processes
    sent = np.zeros(n)
    recv = np.zeros(n)
    if len(s):
        src = np.asarray(s[PROC], np.int64)
        dst = np.asarray(s[PARTNER], np.int64)
        w = np.asarray(s[MSG_SIZE], np.float64) if output == "size" else np.ones(len(s))
        w = np.nan_to_num(w)
        np.add.at(sent, src, w)
        np.add.at(recv, dst, w)
    return EventFrame({PROC: np.arange(n, dtype=np.int32), "sent": sent,
                       "received": recv, "total": sent + recv})


@register_op("comm_over_time")
def comm_over_time(trace, num_bins: int = 32, output: str = "size"
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Message traffic over time (§IV-C): sends binned by timestamp.

    Args:
        num_bins: equal-width time bins over the whole trace span.
        output: ``"size"`` (default) sums bytes per bin; anything else
            counts messages per bin.

    Returns:
        ``(values, edges)``: ``values`` has ``num_bins`` totals, ``edges``
        has ``num_bins + 1`` bin boundaries in ns.
    """
    s = _sends(trace)
    ev = trace.events
    ts_all = np.asarray(ev[TS], np.float64)
    t0 = float(ts_all.min()) if len(ev) else 0.0
    t1 = float(ts_all.max()) if len(ev) else 1.0
    edges = np.linspace(t0, max(t1, t0 + 1), num_bins + 1)
    if len(s) == 0:
        return np.zeros(num_bins), edges
    w = np.asarray(s[MSG_SIZE], np.float64) if output == "size" else np.ones(len(s))
    vals, _ = np.histogram(np.asarray(s[TS], np.float64), bins=edges,
                           weights=np.nan_to_num(w))
    return vals, edges


# ---------------------------------------------------------------------------
# streaming (out-of-core) forms — message aggregates are naturally
# combinable: every send instant carries its (sender, receiver, bytes)
# inline, so per-chunk partial sums merge exactly
# ---------------------------------------------------------------------------

def _chunk_sends(chunk):
    """(src, dst, size) arrays of the send instants in a chunk."""
    ev = chunk.events
    if PARTNER not in ev:
        return None
    sel = ev.cat(NAME).mask_eq(MPI_SEND)
    if not np.any(sel):
        return None
    return (np.asarray(ev[PROC], np.int64)[sel],
            np.asarray(ev[PARTNER], np.int64)[sel],
            np.nan_to_num(np.asarray(ev[MSG_SIZE], np.float64)[sel]),
            np.asarray(ev[TS], np.float64)[sel])


def _check_partner_range(extent: int, n: int, op: str) -> None:
    """The in-memory ops size their output by the selected trace's process
    count and raise on partner ids beyond it (np.add.at IndexError);
    silently truncating here would turn that loud failure into wrong
    zeros — e.g. restrict_processes([0]) then comm_matrix()."""
    if extent > n:
        raise IndexError(
            f"streaming {op}: message partner ids reach process "
            f"{extent - 1} but the selected stream only contains processes "
            f"0..{n - 1}; widen the process restriction to cover message "
            f"partners (the in-memory path fails on this selection too)")


@register_streaming("comm_matrix")
class _CommMatrixAgg(StreamAgg):
    """Combinable comm matrix: per-chunk (sender, receiver) partial sums.
    ``backend="pallas"`` buffers the send records and finalizes through
    :func:`_comm_from_records`, like the eager pallas backend."""

    supports_parallel = True

    def __init__(self, output: str = "size", backend: str = "numpy"):
        self.backend = stream_backend("comm_matrix", backend)
        self.output = output
        self._recs = accel.Records(names=False)
        self._mat = np.zeros((0, 0))
        self._neg = np.zeros(0)  # sends with partner -1, keyed by sender
        self._extent = 0

    def update(self, chunk) -> None:
        s = _chunk_sends(chunk)
        if s is None:
            return
        src, dst, size, ts = s
        w = size if self.output == "size" else np.ones(len(src))
        if self.backend != "numpy":
            pos = dst >= 0
            self._extent = max(self._extent, int(src.max()) + 1,
                               int(dst[pos].max()) + 1 if pos.any() else 0)
            return self._recs.add(ts, ts, src, dst, w)
        neg = dst < 0
        if np.any(neg):
            # the in-memory op's np.add.at wraps dst=-1 into the LAST
            # column of its n×n matrix; n is only known at finalize, so
            # park these per sender and place them then
            n = int(src[neg].max()) + 1
            self._neg = grow_to(self._neg, (n,))
            np.add.at(self._neg, src[neg], w[neg])
            src, dst, w = src[~neg], dst[~neg], w[~neg]
        if not len(src):
            return
        n = int(max(src.max(), dst.max())) + 1
        self._extent = max(self._extent, n)
        self._mat = grow_to(self._mat, (n, n))
        np.add.at(self._mat, (src, dst), w)

    def merge_from(self, other, code_map) -> None:
        # everything is keyed by global process ids — no name remap at all
        self._extent = max(self._extent, other._extent)
        if self.backend != "numpy":
            return self._recs.merge(other._recs, code_map)
        self._mat = grow_to(self._mat, other._mat.shape)
        a, b = other._mat.shape
        self._mat[:a, :b] += other._mat
        self._neg = grow_to(self._neg, other._neg.shape)
        self._neg[: len(other._neg)] += other._neg

    def result(self, ctx) -> np.ndarray:
        n = ctx.num_processes
        _check_partner_range(self._extent, n, "comm_matrix")
        if self.backend != "numpy":
            return _comm_from_records(self._recs, n, "streaming comm_matrix")
        out = np.zeros((max(n, 0), max(n, 0)))
        sub = self._mat[:n, :n]
        out[: sub.shape[0], : sub.shape[1]] = sub
        if n and np.any(self._neg):
            out[: min(n, len(self._neg)), n - 1] += self._neg[:n]
        return out


@register_streaming("comm_by_process")
class _CommByProcessAgg(StreamAgg):
    """Combinable per-process communication volume."""

    supports_parallel = True

    def __init__(self, output: str = "size"):
        self.output = output
        self._sent = np.zeros(0)
        self._recv = np.zeros(0)
        self._neg = 0.0  # receives credited to partner -1 (wraps to last)
        self._extent = 0

    def update(self, chunk) -> None:
        s = _chunk_sends(chunk)
        if s is None:
            return
        src, dst, size, _ts = s
        w = size if self.output == "size" else np.ones(len(src))
        n = int(src.max()) + 1
        self._sent = grow_to(self._sent, (n,))
        np.add.at(self._sent, src, w)
        neg = dst < 0
        if np.any(neg):
            # in-memory np.add.at(recv, -1, w) wraps to the last process
            self._neg += float(w[neg].sum())
            dst, w = dst[~neg], w[~neg]
        if not len(dst):
            return
        n = int(dst.max()) + 1
        self._extent = max(self._extent, n)
        self._recv = grow_to(self._recv, (n,))
        np.add.at(self._recv, dst, w)

    def merge_from(self, other, code_map) -> None:
        self._sent = grow_to(self._sent, other._sent.shape)
        self._sent[: len(other._sent)] += other._sent
        self._recv = grow_to(self._recv, other._recv.shape)
        self._recv[: len(other._recv)] += other._recv
        self._neg += other._neg
        self._extent = max(self._extent, other._extent)

    def result(self, ctx) -> EventFrame:
        n = ctx.num_processes
        _check_partner_range(self._extent, n, "comm_by_process")
        sent = np.zeros(max(n, 0))
        recv = np.zeros(max(n, 0))
        sent[: min(n, len(self._sent))] = self._sent[:n]
        recv[: min(n, len(self._recv))] = self._recv[:n]
        if n:
            recv[n - 1] += self._neg
        return EventFrame({PROC: np.arange(n, dtype=np.int32), "sent": sent,
                           "received": recv, "total": sent + recv})


@register_streaming("message_histogram")
class _MessageHistogramAgg(StreamAgg):
    """Combinable size histogram: a stats pre-pass fixes the [min, max]
    byte range (the same edges ``np.histogram`` derives), then per-chunk
    counts over those edges merge exactly."""

    needs_stats = True
    supports_parallel = True

    def __init__(self, bins: int = 10, backend: str = "numpy"):
        self.backend = stream_backend("message_histogram", backend)
        self.bins = bins
        self._recs = accel.Records(names=False)
        self._counts = np.zeros(bins, np.int64)
        self._edges: Optional[np.ndarray] = None

    def begin(self, stats) -> None:
        if stats.n_sends == 0:
            return
        self._edges = np.histogram_bin_edges(
            np.asarray([stats.size_min, stats.size_max]), bins=self.bins,
            range=(stats.size_min, stats.size_max))

    def update(self, chunk) -> None:
        if self._edges is None:
            return
        s = _chunk_sends(chunk)
        if s is None:
            return
        src, dst, size, ts = s
        if self.backend != "numpy":
            return self._recs.add(ts, ts, src, dst, size)
        c, _ = np.histogram(size, bins=self._edges)
        self._counts += c

    def merge_from(self, other, code_map) -> None:
        # edges were fixed by the shared stats pre-pass; counts just add
        if self.backend != "numpy":
            return self._recs.merge(other._recs, code_map)
        self._counts += other._counts

    def result(self, ctx) -> Tuple[np.ndarray, np.ndarray]:
        if self._edges is None:
            return np.zeros(self.bins, np.int64), np.linspace(0, 1,
                                                              self.bins + 1)
        if self.backend != "numpy":
            *_, sizes = self._recs.columns()
            return accel.hist_counts(
                _hist_indices(sizes, self._edges, self.bins),
                self.bins), self._edges
        return self._counts, self._edges


@register_streaming("comm_over_time")
class _CommOverTimeAgg(StreamAgg):
    """Combinable traffic-over-time: bin edges come from the stats pre-pass
    (whole-stream time span), per-chunk weighted histograms merge exactly
    for integer byte counts."""

    needs_stats = True
    supports_parallel = True

    def __init__(self, num_bins: int = 32, output: str = "size"):
        self.num_bins = num_bins
        self.output = output
        self._vals = np.zeros(num_bins)
        self._edges: Optional[np.ndarray] = None

    def begin(self, stats) -> None:
        t0 = stats.ts_min if stats.n_events else 0.0
        t1 = stats.ts_max if stats.n_events else 1.0
        self._edges = np.linspace(t0, max(t1, t0 + 1), self.num_bins + 1)

    def update(self, chunk) -> None:
        s = _chunk_sends(chunk)
        if s is None:
            return
        _src, _dst, size, ts = s
        w = size if self.output == "size" else np.ones(len(ts))
        v, _ = np.histogram(ts, bins=self._edges, weights=w)
        self._vals += v

    def merge_from(self, other, code_map) -> None:
        self._vals += other._vals

    def result(self, ctx) -> Tuple[np.ndarray, np.ndarray]:
        return self._vals, self._edges


def comm_name_mask(events: EventFrame,
                   prefixes: Sequence[str] = DEFAULT_COMM_PREFIXES) -> np.ndarray:
    """Boolean mask over the *category table* rows mapped to events: True where
    the event's function name looks like communication."""
    cat = events.cat(NAME)
    subs = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
            "collective-permute", "nccl", "send", "recv")
    is_comm_cat = np.zeros(len(cat.categories), dtype=bool)
    for i, c in enumerate(cat.categories):
        cs = str(c)
        low = cs.lower()
        is_comm_cat[i] = cs.startswith(tuple(prefixes)) or any(s in low for s in subs)
    return is_comm_cat[cat.codes]


@register_op("comm_comp_breakdown", needs_structure=True)
def comm_comp_breakdown(trace, comm_matcher: Optional[Callable[[str], bool]] = None
                        ) -> EventFrame:
    """Per-process split of wall time into non-overlapped computation,
    computation overlapped with communication, non-overlapped communication,
    and other/idle (§IV-C, Fig. 13).

    Communication and computation can only overlap across threads/streams of
    the same process (e.g. a compute stream and a NCCL stream); interval
    algebra over the merged per-class interval sets yields the split.

    Args:
        comm_matcher: ``fn(name) -> bool`` deciding which functions count
            as communication; default matches MPI/NCCL/collective name
            patterns (see :func:`comm_name_mask`).

    Returns:
        EventFrame with one row per process: ``Process``, ``comp_only``,
        ``overlap``, ``comm_only``, ``other`` (unaccounted/idle), and
        ``span`` (the process's wall-clock extent) — all in ns, with
        ``comp_only + overlap + comm_only + other == span``.
    """
    ev = trace.events
    n = len(ev)
    procs = np.asarray(ev[PROC], np.int64)
    ts = np.asarray(ev[TS], np.float64)
    match = np.asarray(ev.column("_matching_event"), np.int64)
    is_enter = ev.cat(ET).mask_eq(ENTER)

    if comm_matcher is None:
        comm_mask = comm_name_mask(ev)
    else:
        cat = ev.cat(NAME)
        per_cat = np.asarray([bool(comm_matcher(str(c))) for c in cat.categories])
        comm_mask = per_cat[cat.codes]

    # leaf calls: matched enters with no child enter inside → use exclusive
    # spans approximated by call spans of *leaf* calls to avoid double count.
    parent = np.asarray(ev.column("_parent"), np.int64)
    has_child = np.zeros(n, dtype=bool)
    pe = parent[(parent >= 0) & is_enter]
    has_child[pe[pe >= 0]] = True

    sel = np.nonzero(is_enter & (match >= 0))[0]
    leaf = sel[~has_child[sel]]
    comm_leaf = leaf[comm_mask[leaf]]
    comp_leaf = leaf[~comm_mask[leaf]]
    # a call that *contains* only comm children is itself comm plumbing; treat
    # non-leaf comm calls' spans as comm too (covers MPI_Wait around Isend).
    comm_any = sel[comm_mask[sel]]

    nprocs = trace.num_processes
    cols = {k: np.zeros(nprocs) for k in
            ("comp_only", "overlap", "comm_only", "other", "span")}
    for p in range(nprocs):
        def spans(rows):
            rows = rows[procs[rows] == p]
            return merge_intervals(ts[rows], ts[match[rows]])
        comm_iv = spans(comm_any)
        comp_iv = spans(comp_leaf)
        p_rows = np.nonzero(procs == p)[0]
        if len(p_rows) == 0:
            continue
        span = float(ts[p_rows].max() - ts[p_rows].min())
        lcomm = float(np.sum(comm_iv[1] - comm_iv[0]))
        lcomp = float(np.sum(comp_iv[1] - comp_iv[0]))
        us, ue = merge_intervals(np.concatenate([comm_iv[0], comp_iv[0]]),
                                 np.concatenate([comm_iv[1], comp_iv[1]]))
        lunion = float(np.sum(ue - us))
        ov = lcomm + lcomp - lunion
        cols["overlap"][p] = ov
        cols["comm_only"][p] = lcomm - ov
        cols["comp_only"][p] = lcomp - ov
        cols["other"][p] = max(span - lunion, 0.0)
        cols["span"][p] = span
    out = EventFrame({PROC: np.arange(nprocs, dtype=np.int32)})
    for k, v in cols.items():
        out[k] = v
    return out
