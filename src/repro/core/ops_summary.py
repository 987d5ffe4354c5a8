"""Summary/aggregation operations (paper §IV-B, §IV-D in part).

All functions take a Trace whose structure columns (matching, parent,
time.inc/time.exc) are already materialized; Trace methods guarantee that.

Each op with a combinable partial-aggregate form also registers a streaming
aggregator (``register_streaming``) so the out-of-core executor
(:mod:`repro.core.streaming`) can run it chunk by chunk over traces that do
not fit in RAM; the aggregators reproduce the in-memory results (exactly,
for integer-ns traces — see docs/streaming.md).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from . import accel
from .constants import (DEFAULT_IDLE_NAMES, ENTER, ET, EXC, INC, NAME, PROC, TS)
from .frame import Categorical, EventFrame
from .registry import (get_backend, op_backends, register_backend,
                       register_op, register_streaming)
from .streaming import StreamAgg, StreamingUnsupported, grow_to
from ..runtime import tracer


# ---------------------------------------------------------------------------
# time_profile backends (the prototype of the per-op backend registry)
# ---------------------------------------------------------------------------

#: the live ``time_profile`` backend table — an alias of
#: ``registry.op_backends("time_profile")`` kept for backwards
#: compatibility (mutating it *is* registration).  A backend maps call
#: records onto the [bins, functions] overlap matrix:
#: ``fn(starts, ends, rate, name_codes, edges, nf) -> np.ndarray``
#: with ``starts``/``ends`` float64 ns, ``rate`` weight/ns, ``name_codes``
#: int codes < nf, ``edges`` the bin edge array (len num_bins+1).
TIME_PROFILE_BACKENDS: Dict[str, Callable[..., np.ndarray]] = \
    op_backends("time_profile")


def register_time_profile_backend(name: str) -> Callable:
    """Decorator registering a ``time_profile(backend=<name>)`` accumulation
    backend (last registration wins, like the op registry).  Equivalent to
    ``registry.register_backend("time_profile", name)``."""
    return register_backend("time_profile", name)


@register_op("flat_profile", needs_structure=True)
def flat_profile(trace, metrics: Sequence[str] = (EXC,), groupby_column: str = NAME,
                 per_process: bool = False, backend: str = "numpy") -> EventFrame:
    """Total metric per function, aggregated over the whole trace (§IV-B).

    Sums each metric over every *matched call* (Enter event) of a function,
    across all processes unless ``per_process``.

    Args:
        metrics: metric columns to sum — ``time.exc`` (default; ns the
            function spent in its own code, callees excluded) and/or
            ``time.inc`` (ns including callees; inclusive sums over nested
            calls of the same function double-count by design).
        groupby_column: grouping key (default ``Name``; any categorical
            column works, e.g. a custom phase column).
        per_process: additionally group by ``Process`` (one row per
            (function, process) pair).
        backend: ``"numpy"`` (default, exact) or ``"pallas"`` (one-hot
            matmul segment-sum kernel, f32 rounding; see docs/kernels.md).

    Returns:
        EventFrame with the group key column(s), one summed column per
        metric (ns), and ``count`` (number of calls), sorted by the first
        metric descending.
    """
    return get_backend("flat_profile", backend)(
        trace, metrics=metrics, groupby_column=groupby_column,
        per_process=per_process)


@register_backend("flat_profile", "numpy")
def _flat_profile_numpy(trace, *, metrics: Sequence[str] = (EXC,),
                        groupby_column: str = NAME,
                        per_process: bool = False) -> EventFrame:
    """The exact reference: one groupby over every Enter row."""
    ev = trace.events
    ent = ev.mask(ev.cat(ET).mask_eq(ENTER))
    keys = [groupby_column, PROC] if per_process else [groupby_column]
    aggs = {m: "sum" for m in metrics}
    prof = ent.groupby_agg(keys, aggs, count_name="count")
    # NaN-safe: unmatched enters carry NaN metrics
    for m in metrics:
        prof[m] = np.nan_to_num(prof[m])
    order = np.argsort(-prof[metrics[0]], kind="stable")
    return prof.take(order)


def _flat_assemble(names_alpha, counts, sums, metrics, per_process
                   ) -> EventFrame:
    """Shared finalization of the record-level flat_profile paths: counts
    (exact int64) and per-metric sums, both on the alphabetical name axis,
    become the output frame.  Used by the streaming aggregator and the
    pallas backend on every path — identical assembly is half of the
    digest-identity contract."""
    out = EventFrame()
    if per_process:
        f_alpha, p_alpha = np.nonzero(counts)
        out[NAME] = Categorical(f_alpha.astype(np.int32), names_alpha)
        out[PROC] = p_alpha.astype(np.int64)
        out["count"] = counts[f_alpha, p_alpha]
        for i, m in enumerate(metrics):
            out[m] = sums[i, f_alpha, p_alpha]
    else:
        present = np.nonzero(counts)[0]
        out[NAME] = Categorical(present.astype(np.int32), names_alpha)
        out["count"] = counts[present]
        for i, m in enumerate(metrics):
            out[m] = sums[i, present]
    order = np.argsort(-np.asarray(out[metrics[0]]), kind="stable")
    return out.take(order)


@register_backend("flat_profile", "pallas")
def _flat_profile_pallas(trace, *, metrics: Sequence[str] = (EXC,),
                         groupby_column: str = NAME,
                         per_process: bool = False) -> EventFrame:
    """Accelerator flat profile: canonical-ordered completed-call records
    through the seg_sum / pair_sum one-hot-matmul kernels.  Counts stay
    exact (host int64); metric sums agree with numpy to f32 rounding."""
    if groupby_column != NAME:
        raise ValueError(
            f"flat_profile backend='pallas' groups by {NAME!r} only, got "
            f"groupby_column={groupby_column!r}; use backend='numpy'")
    metrics = list(metrics)
    ev = trace.events
    is_enter = ev.cat(ET).mask_eq(ENTER)
    match = np.asarray(ev.column("_matching_event"), np.int64)
    ts = np.asarray(ev[TS], np.float64)
    codes = ev.codes(NAME)
    procs = np.asarray(ev[PROC], np.int64)
    names_alpha, _order, inv = accel.alpha_positions(ev.cat(NAME).categories)
    nf = len(names_alpha)
    nprocs = max(trace.num_processes, 1)

    ent = np.nonzero(is_enter)[0]
    acode_all = inv[codes[ent]]
    if per_process:
        counts = np.zeros((nf, nprocs), np.int64)
        np.add.at(counts, (acode_all, procs[ent]), 1)
    else:
        counts = np.bincount(acode_all, minlength=nf).astype(np.int64)

    # kernel records: matched calls only (unmatched enters contribute
    # exactly 0 to the numpy sums; the NaN-poisoning they cause is applied
    # per metric below, mirroring nan_to_num-after-groupby)
    msel = np.nonzero(is_enter & (match >= 0))[0]
    vals = np.stack([np.nan_to_num(
        np.asarray(ev.column(m), np.float64)[msel]) for m in metrics],
        axis=1)
    acode = inv[codes[msel]]
    pr = procs[msel]
    o = accel.canonical_order(ts[msel], ts[match[msel]], pr, acode,
                              vals[:, 0])
    if per_process:
        sums = np.stack([accel.pair_sum(acode[o], pr[o], vals[o, i],
                                        nf, nprocs)
                         for i in range(len(metrics))])
    else:
        sums = accel.seg_sum(acode[o], vals[o], nf).T
    for i, m in enumerate(metrics):
        bad = np.isnan(np.asarray(ev.column(m), np.float64)[ent])
        if bad.any():
            if per_process:
                sums[i][acode_all[bad], procs[ent][bad]] = 0.0
            else:
                sums[i][acode_all[bad]] = 0.0
    return _flat_assemble(names_alpha, counts, sums, metrics, per_process)


@register_op("time_profile", needs_structure=True)
def time_profile(trace, num_bins: int = 32, metric: str = EXC,
                 normalized: bool = False, backend: str = "numpy") -> EventFrame:
    """Flat profile over time (§IV-B): bins × functions matrix.

    Each matched call contributes its metric, modeled as uniformly spread
    over its [enter, leave) span; the trace's [t_min, t_max] is divided
    into ``num_bins`` equal bins.  Exact O(N + bins·functions) NumPy sweep
    (no N×bins matrix); ``backend="pallas"`` routes the dense tiled kernel
    in repro.kernels.time_bin (TPU target; interpret-mode on CPU).

    Args:
        num_bins: number of equal-width time bins.
        metric: ``time.exc`` (default) or ``time.inc``, in ns.
        normalized: scale each bin's values to fractions of that bin's
            total (rows sum to 1 where any time was recorded).
        backend: a backend registered in :data:`TIME_PROFILE_BACKENDS`
            (the live ``registry.op_backends("time_profile")`` table) —
            built-ins are ``"numpy"`` (exact sweep) and ``"pallas"``
            (tiled kernel); register your own with
            :func:`register_time_profile_backend`.  Non-numpy backends
            run on canonically ordered call records, so every execution
            path (eager, streaming, parallel, pack) produces an
            identical frame.

    Returns:
        EventFrame with ``bin_start``/``bin_end`` (ns) plus one column per
        function holding its per-bin metric (ns, or fractions when
        ``normalized``), columns ordered by total weight descending.
    """
    ev = trace.events
    ts = np.asarray(ev[TS], np.float64)
    if len(ev) == 0:
        return EventFrame({"bin_start": np.asarray([]), "bin_end": np.asarray([])})
    t0, t1 = float(ts.min()), float(ts.max())
    if t1 <= t0:
        t1 = t0 + 1.0
    edges = np.linspace(t0, t1, num_bins + 1)

    is_enter = ev.cat(ET).mask_eq(ENTER)
    match = np.asarray(ev.column("_matching_event"), np.int64)
    sel = np.nonzero(is_enter & (match >= 0))[0]
    starts = ts[sel]
    ends = ts[match[sel]]
    w = np.nan_to_num(np.asarray(ev.column(metric), np.float64)[sel])
    name_codes = ev.codes(NAME)[sel]
    cats = ev.cat(NAME).categories
    nf = len(cats)

    fn = get_backend("time_profile", backend)
    if backend != "numpy":
        # record-level path shared with the streaming finalizer: canonical
        # order + alphabetical code space ⇒ identical frames on every path
        names_alpha, _order, inv = accel.alpha_positions(cats)
        procs = np.asarray(ev[PROC], np.int64)[sel]
        return _profile_from_records(starts, ends, w, procs,
                                     inv[name_codes], names_alpha, edges,
                                     num_bins, normalized, fn)

    inc = ends - starts
    rate = np.where(inc > 0, w / np.maximum(inc, 1e-30), 0.0)
    prof = fn(starts, ends, rate, name_codes, edges, nf)

    # zero-duration calls: all weight in their bin
    zsel = inc <= 0
    if np.any(zsel & (w > 0)):
        b = np.clip(np.searchsorted(edges, starts[zsel], side="right") - 1, 0, num_bins - 1)
        np.add.at(prof, (b, name_codes[zsel]), w[zsel])

    if normalized:
        denom = prof.sum(axis=1, keepdims=True)
        prof = prof / np.maximum(denom, 1e-30)
    out = EventFrame({"bin_start": edges[:-1], "bin_end": edges[1:]})
    keep = np.nonzero(prof.sum(axis=0) > 0)[0]
    order = keep[np.argsort(-prof[:, keep].sum(axis=0), kind="stable")]
    for f in order:
        out[str(cats[f])] = prof[:, f]
    return out


def _profile_from_records(starts, ends, w, procs, acodes, names_alpha,
                          edges, num_bins, normalized, fn) -> EventFrame:
    """Record-level ``time_profile`` core for non-numpy backends, shared by
    the eager op and the streaming finalizer: canonical-sort the call
    records, invoke the backend once, apply the zero-duration fixup and
    assemble columns in the alphabetical code space.  Both paths hold the
    same record multiset, so the resulting frames are identical."""
    o = accel.canonical_order(starts, ends, procs, acodes, w)
    starts, ends, w, acodes = starts[o], ends[o], w[o], acodes[o]
    inc = ends - starts
    rate = np.where(inc > 0, w / np.maximum(inc, 1e-30), 0.0)
    prof = np.asarray(fn(starts, ends, rate, acodes, edges,
                         len(names_alpha)), np.float64)
    zsel = inc <= 0
    if np.any(zsel & (w > 0)):
        b = np.clip(np.searchsorted(edges, starts[zsel], side="right") - 1,
                    0, num_bins - 1)
        np.add.at(prof, (b, acodes[zsel]), w[zsel])
    if normalized:
        denom = prof.sum(axis=1, keepdims=True)
        prof = prof / np.maximum(denom, 1e-30)
    out = EventFrame({"bin_start": edges[:-1], "bin_end": edges[1:]})
    keep = np.nonzero(prof.sum(axis=0) > 0)[0]
    order = keep[np.argsort(-prof[:, keep].sum(axis=0), kind="stable")]
    for f in order:
        out[str(names_alpha[f])] = prof[:, f]
    return out


@register_time_profile_backend("pallas")
def _pallas_profile(starts, ends, rate, name_codes, edges, nf) -> np.ndarray:
    """The Pallas TPU kernel (repro.kernels.time_bin): scatter-free one-hot
    matmul accumulation, interpret-mode on CPU.  Values agree with the
    exact sweep to f32 rounding."""
    from ..kernels.ops import time_profile_matrix
    num_bins = len(edges) - 1
    t0, t1 = float(edges[0]), float(edges[-1])
    # normalize to bin units: f32 kernel arithmetic loses ns-scale
    # precision at bin boundaries otherwise
    bw = (t1 - t0) / num_bins
    if not (bw > 0) or not np.isfinite(bw):
        # degenerate span (all edges equal, e.g. a single-instant trace fed
        # directly): every overlap is zero — dividing by bw would turn that
        # into NaN where the numpy backend returns zeros
        return np.zeros((num_bins, nf))
    with tracer.span("accel.kernel", kernel="time_bin"):
        ops = ((starts - t0) / bw, (ends - t0) / bw, name_codes, rate * bw)
        out = np.asarray(time_profile_matrix(
            *ops, n_funcs=nf, n_bins=num_bins, t0=0.0, t1=float(num_bins),
            be=accel.block_size(len(starts), nf + num_bins))).T
    accel.count_upload(*ops)
    return out


@register_time_profile_backend("numpy")
def _exact_profile(starts, ends, rate, name_codes, edges, nf) -> np.ndarray:
    """C(t) = Σ rate_i·clamp(t−s_i, 0, e_i−s_i) evaluated at edges, per name.

    Decomposed into five cumulative histograms so cost is O(N + bins·names):
      C(t) = t·(P−Q) − (Ps−Qs) + R
    with P=Σr·1[s≤t], Q=Σr·1[e≤t], Ps=Σr·s·1[s≤t], Qs=Σr·s·1[e≤t],
    R=Σr·(e−s)·1[e≤t].
    """
    nb = len(edges) - 1
    # index of first edge >= value  →  contributes to cumulative at that edge on
    si = np.searchsorted(edges, starts, side="left")
    ei = np.searchsorted(edges, ends, side="left")
    H = np.zeros((5, nb + 2, nf))
    np.add.at(H[0], (si, name_codes), rate)                    # P
    np.add.at(H[1], (ei, name_codes), rate)                    # Q
    np.add.at(H[2], (si, name_codes), rate * starts)           # Ps
    np.add.at(H[3], (ei, name_codes), rate * starts)           # Qs
    np.add.at(H[4], (ei, name_codes), rate * (ends - starts))  # R
    cum = np.cumsum(H[:, : nb + 1, :], axis=1)  # value at each edge
    t = edges[:, None]
    C = t * (cum[0] - cum[1]) - (cum[2] - cum[3]) + cum[4]
    return np.maximum(np.diff(C, axis=0), 0.0)


@register_op("load_imbalance", needs_structure=True)
def load_imbalance(trace, metric: str = EXC, num_processes: int = 5,
                   top_functions: Optional[int] = None,
                   backend: str = "numpy") -> EventFrame:
    """Per-function load imbalance across processes (§IV-D, Fig. 7).

    For each function, sums the metric per process and reports
    max-over-processes / mean-over-processes — 1.0 is perfectly balanced,
    2.0 means the busiest process carries twice the average.

    Args:
        metric: ``time.exc`` (default) or ``time.inc``, in ns.
        num_processes: how many of the busiest process ids to list per
            function (does not affect the ratio).
        top_functions: truncate to the N functions with the largest mean
            metric (None = all functions with any time).
        backend: ``"numpy"`` (default, exact) or ``"pallas"`` (pair_sum
            one-hot matmul kernel, f32 rounding; see docs/kernels.md).

    Returns:
        EventFrame sorted by mean metric descending with ``Name``,
        ``<metric>.imbalance`` (the max/mean ratio), ``Top processes``
        (list of the heaviest process ids), ``<metric>.mean`` and
        ``<metric>.max`` (ns).
    """
    return get_backend("load_imbalance", backend)(
        trace, metric=metric, num_processes=num_processes,
        top_functions=top_functions)


def _imbalance_assemble(tot, names_alpha, metric, num_processes,
                        top_functions, nprocs) -> EventFrame:
    """Shared finalization of load_imbalance: the per-(function, process)
    totals matrix (name-code-aligned with ``names_alpha``) becomes the
    ranked imbalance frame — one implementation for the eager backends and
    the streaming finalizer."""
    nf = tot.shape[0]
    active = tot.sum(axis=1) > 0
    mean = tot.sum(axis=1) / max(nprocs, 1)
    mx = tot.max(axis=1) if tot.size else np.zeros(nf)
    imb = np.where(mean > 0, mx / np.maximum(mean, 1e-30), 0.0)
    topk = np.argsort(-tot, axis=1)[:, :num_processes]
    sel = np.nonzero(active)[0]
    order = sel[np.argsort(-mean[sel], kind="stable")]
    if top_functions:
        order = order[:top_functions]
    return EventFrame({
        NAME: Categorical(order.astype(np.int32), names_alpha),
        f"{metric}.imbalance": imb[order],
        "Top processes": np.asarray([list(map(int, topk[i])) for i in order], dtype=object),
        f"{metric}.mean": mean[order],
        f"{metric}.max": mx[order],
    })


@register_backend("load_imbalance", "numpy")
def _load_imbalance_numpy(trace, *, metric: str = EXC,
                          num_processes: int = 5,
                          top_functions: Optional[int] = None) -> EventFrame:
    """The exact reference: one scatter-add over every Enter row."""
    ev = trace.events
    ent = ev.mask(ev.cat(ET).mask_eq(ENTER))
    vals = np.nan_to_num(np.asarray(ent.column(metric), np.float64))
    names = ent.codes(NAME)
    procs = np.asarray(ent[PROC], np.int64)
    cats = ent.cat(NAME).categories
    nprocs = trace.num_processes
    nf = len(cats)
    tot = np.zeros((nf, nprocs))
    np.add.at(tot, (names, procs), vals)
    return _imbalance_assemble(tot, cats, metric, num_processes,
                               top_functions, nprocs)


@register_backend("load_imbalance", "pallas")
def _load_imbalance_pallas(trace, *, metric: str = EXC,
                           num_processes: int = 5,
                           top_functions: Optional[int] = None
                           ) -> EventFrame:
    """Accelerator load imbalance: canonical-ordered completed-call records
    through the pair_sum one-hot-matmul kernel (function × rank totals to
    f32 rounding; unmatched enters contribute exactly 0 in the reference
    and are simply dropped here)."""
    ev = trace.events
    ts = np.asarray(ev[TS], np.float64)
    is_enter = ev.cat(ET).mask_eq(ENTER)
    match = np.asarray(ev.column("_matching_event"), np.int64)
    sel = np.nonzero(is_enter & (match >= 0))[0]
    vals = np.nan_to_num(np.asarray(ev.column(metric), np.float64)[sel])
    names_alpha, _order, inv = accel.alpha_positions(ev.cat(NAME).categories)
    acode = inv[ev.codes(NAME)[sel]]
    procs = np.asarray(ev[PROC], np.int64)[sel]
    nprocs = trace.num_processes
    o = accel.canonical_order(ts[sel], ts[match[sel]], procs, acode, vals)
    tot = accel.pair_sum(acode[o], procs[o], vals[o], len(names_alpha),
                         max(nprocs, 1))
    return _imbalance_assemble(tot, names_alpha, metric, num_processes,
                               top_functions, nprocs)


@register_op("idle_time", needs_structure=True)
def idle_time(trace, idle_functions: Sequence[str] = DEFAULT_IDLE_NAMES,
              k: Optional[int] = None) -> EventFrame:
    """Total idle (wait/recv) time per process (§IV-D), sorted descending.

    Sums the *inclusive* time (ns) of every call whose name is in
    ``idle_functions`` — inclusive, because the whole span of an MPI_Wait
    counts as idle regardless of what bookkeeping runs inside it.

    Args:
        idle_functions: names treated as idleness (default: MPI_Wait,
            MPI_Waitall, MPI_Recv, Idle, MPI_Barrier).
        k: keep only the k most-idle processes (None = all).

    Returns:
        EventFrame with ``Process`` and ``idle_time`` (ns), most idle first.
    """
    ev = trace.events
    ent_mask = ev.cat(ET).mask_eq(ENTER) & ev.cat(NAME).mask_isin(idle_functions)
    ent = ev.mask(ent_mask)
    nprocs = trace.num_processes
    out = np.zeros(nprocs)
    np.add.at(out, np.asarray(ent[PROC], np.int64),
              np.nan_to_num(np.asarray(ent.column(INC), np.float64)))
    order = np.argsort(-out, kind="stable")
    res = EventFrame({PROC: order.astype(np.int32), "idle_time": out[order]})
    return res.head(k) if k else res


# ---------------------------------------------------------------------------
# streaming (out-of-core) forms — combinable partial aggregates per chunk
# ---------------------------------------------------------------------------

_CALL_METRICS = (INC, EXC)


def _check_metric(metric: str, op: str) -> None:
    if metric not in _CALL_METRICS:
        raise StreamingUnsupported(
            f"streaming {op} supports metrics {_CALL_METRICS}, got "
            f"{metric!r}; materialize with .collect() for custom metrics")


def _alpha(ctx, nf: int):
    """(sorted names, gather order, code→alphabetical-position map) over the
    first ``nf`` global codes — restores the category-code group order the
    in-memory groupby produces.  ``arr[order]`` re-orders a code-indexed
    axis alphabetically; ``inv[code]`` is a code's alphabetical position."""
    names = np.asarray(ctx.names.names[:nf], dtype=object).astype(str)
    order = np.argsort(names, kind="stable")
    inv = np.empty(nf, np.int64)
    inv[order] = np.arange(nf)
    return names[order], order, inv


def _pad_to(arr: np.ndarray, shape) -> np.ndarray:
    """Zero-padded copy of ``arr`` with exactly ``shape`` (accumulators may
    be under-grown when late chunks discovered names but produced no calls,
    and over-grown by the power-of-two capacity)."""
    out = np.zeros(shape, dtype=arr.dtype)
    sub = arr[tuple(slice(0, min(a, s)) for a, s in zip(arr.shape, shape))]
    out[tuple(slice(0, n) for n in sub.shape)] = sub
    return out


def _scatter_names(dst: np.ndarray, src: np.ndarray, code_map: np.ndarray,
                   axis: int) -> np.ndarray:
    """Add ``src`` (a worker accumulator whose ``axis`` is indexed by the
    worker's local name codes) into ``dst`` with that axis remapped through
    ``code_map`` — the shared kernel of every cross-worker ``merge_from``.
    ``src`` is padded to exactly ``len(code_map)`` names (and ``dst``'s
    extents on the other axes); ``dst`` is grown to hold the remapped codes.
    ``code_map`` entries are unique, so a fancy-indexed ``+=`` is exact.
    """
    k = len(code_map)
    if k == 0:
        return dst
    want = list(dst.shape)
    for ax in range(dst.ndim):
        if ax == axis:
            want[ax] = k
        else:
            want[ax] = max(want[ax], src.shape[ax] if ax < src.ndim else 0)
    src = _pad_to(src, tuple(want))
    grown = list(src.shape)
    grown[axis] = int(code_map.max()) + 1
    dst = grow_to(dst, tuple(grown))
    idx = [slice(0, n) for n in src.shape]
    idx[axis] = code_map
    dst[tuple(idx)] += src
    return dst


@register_streaming("flat_profile")
class _FlatProfileAgg(StreamAgg):
    """Combinable flat profile: per-name (or per name×process) metric sums
    over completed calls plus call counts over every Enter row.  Sums of
    integer-ns metrics are exact in float64 (< 2⁵³), so merging partials is
    order-independent and the result matches the in-memory op bit for bit.
    A name with an unmatched Enter reproduces the in-memory NaN-poisoning:
    its group total collapses to 0 (``nan_to_num`` after aggregation).

    ``backend="pallas"`` buffers the completed-call records instead of
    accumulating sums, then canonical-sorts and invokes the kernel once at
    finalize — exactly what the eager pallas backend does, so the two paths
    produce byte-identical frames (counts stay exact either way)."""

    needs_calls = True
    supports_parallel = True

    def __init__(self, metrics: Sequence[str] = (EXC,),
                 groupby_column: str = NAME, per_process: bool = False,
                 backend: str = "numpy"):
        if groupby_column != NAME:
            raise StreamingUnsupported(
                f"streaming flat_profile groups by {NAME!r} only, got "
                f"groupby_column={groupby_column!r}")
        get_backend("flat_profile", backend)  # fail fast on unknown names
        if backend not in ("numpy", "pallas"):
            raise StreamingUnsupported(
                f"streaming flat_profile supports backends ('numpy', "
                f"'pallas'); {backend!r} is trace-level — materialize with "
                f".collect() to use it")
        self.backend = backend
        self.metrics = list(metrics)
        for m in self.metrics:
            _check_metric(m, "flat_profile")
        self.per_process = per_process
        nm = len(self.metrics)
        self._recs: List[tuple] = []
        if per_process:
            self._counts = np.zeros((0, 0), np.int64)
            self._sums = np.zeros((nm, 0, 0))
        else:
            self._counts = np.zeros(0, np.int64)
            self._sums = np.zeros((nm, 0))

    def update(self, chunk) -> None:
        ev = chunk.events
        is_enter = ev.cat(ET).mask_eq(ENTER)
        codes = chunk.gcodes[is_enter]
        calls = chunk.calls
        nf = len(chunk.names)
        metric_vals = {INC: calls.inc, EXC: calls.exc}
        if self.backend != "numpy":
            vals = np.stack([np.nan_to_num(metric_vals[m])
                             for m in self.metrics], axis=1) \
                if len(calls.name) else np.zeros((0, len(self.metrics)))
            self._recs.append((calls.name.copy(), calls.proc.copy(),
                               calls.start.copy(), calls.end.copy(), vals))
        if self.per_process:
            procs = np.asarray(ev[PROC], np.int64)[is_enter]
            np_ = int(max(procs.max() + 1 if len(procs) else 0,
                          calls.proc.max() + 1 if len(calls.proc) else 0))
            self._counts = grow_to(self._counts, (nf, np_))
            np.add.at(self._counts, (codes, procs), 1)
            if self.backend == "numpy":
                self._sums = grow_to(self._sums,
                                     (self._sums.shape[0], nf, np_))
                for i, m in enumerate(self.metrics):
                    np.add.at(self._sums[i], (calls.name, calls.proc),
                              metric_vals[m])
        else:
            self._counts = grow_to(self._counts, (nf,))
            np.add.at(self._counts, codes, 1)
            if self.backend == "numpy":
                self._sums = grow_to(self._sums, (self._sums.shape[0], nf))
                for i, m in enumerate(self.metrics):
                    np.add.at(self._sums[i], calls.name, metric_vals[m])

    def merge_from(self, other, code_map) -> None:
        # counts/sums lead with the name axis in both layouts; procs (when
        # present) are global ids and need no remap
        self._counts = _scatter_names(self._counts, other._counts, code_map,
                                      axis=0)
        if self.backend == "numpy":
            self._sums = _scatter_names(self._sums, other._sums, code_map,
                                        axis=1)
        else:
            for name, proc, start, end, vals in other._recs:
                self._recs.append((code_map[name], proc, start, end, vals))

    def _gather_records(self, inv):
        """Concatenate the buffered call records into flat arrays with
        alphabetical name positions — shared by the pallas finalizers."""
        if self._recs:
            name = np.concatenate([r[0] for r in self._recs])
            proc = np.concatenate([r[1] for r in self._recs])
            start = np.concatenate([r[2] for r in self._recs])
            end = np.concatenate([r[3] for r in self._recs])
            vals = np.concatenate([r[4] for r in self._recs])
        else:
            name = proc = np.zeros(0, np.int64)
            start = end = np.zeros(0)
            vals = np.zeros((0, len(self.metrics)))
        return inv[name], proc, start, end, vals

    def result(self, ctx) -> EventFrame:
        nf = len(ctx.names)
        if self.backend == "numpy" and (nf == 0 or not np.any(self._counts)):
            out = EventFrame()
            out[NAME] = np.asarray([])
            for m in self.metrics:
                out[m] = np.asarray([])
            return out
        names_alpha, order, inv = _alpha(ctx, nf)
        open_names, open_procs = ctx.open_calls
        nm = len(self.metrics)
        if self.per_process:
            np_ = max(self._counts.shape[1], self._sums.shape[2],
                      ctx.num_processes, 1)
            counts = _pad_to(self._counts, (nf, np_))[order]
            if self.backend == "numpy":
                sums = _pad_to(self._sums, (nm, nf, np_))[:, order]
            else:
                acode, proc, start, end, vals = self._gather_records(inv)
                o = accel.canonical_order(start, end, proc, acode,
                                          vals[:, 0] if nm else start)
                sums = np.stack([accel.pair_sum(acode[o], proc[o],
                                                vals[o, i], nf, np_)
                                 for i in range(nm)]) \
                    if nm else np.zeros((0, nf, np_))
            if len(open_names):
                sums[:, inv[open_names], open_procs] = 0.0
        else:
            counts = _pad_to(self._counts, (nf,))[order]
            if self.backend == "numpy":
                sums = _pad_to(self._sums, (nm, nf))[:, order]
            else:
                acode, proc, start, end, vals = self._gather_records(inv)
                o = accel.canonical_order(start, end, proc, acode,
                                          vals[:, 0] if nm else start)
                sums = accel.seg_sum(acode[o], vals[o], nf).T
            if len(open_names):
                sums[:, inv[open_names]] = 0.0
        return _flat_assemble(names_alpha, counts, sums, self.metrics,
                              self.per_process)


@register_streaming("time_profile")
class _TimeProfileAgg(StreamAgg):
    """Combinable time profile: the exact five-histogram decomposition of
    the in-memory op, accumulated per chunk over completed calls.  A stats
    pre-pass fixes the global [t_min, t_max] bin edges first (the stream is
    read twice; peak memory stays bounded).  Partial-sum order differs from
    the in-memory single pass, so values agree to float64 rounding, not
    necessarily bit-for-bit.

    Non-numpy backends (record-level contract) buffer the completed-call
    records and run :func:`_profile_from_records` at finalize — the same
    canonical-sort + single-kernel-call core the eager op uses, so e.g.
    ``backend="pallas"`` yields byte-identical frames on both paths."""

    needs_calls = True
    needs_stats = True
    supports_parallel = True

    def __init__(self, num_bins: int = 32, metric: str = EXC,
                 normalized: bool = False, backend: str = "numpy"):
        _check_metric(metric, "time_profile")
        self._fn = get_backend("time_profile", backend)
        self.backend = backend
        self.num_bins = num_bins
        self.metric = metric
        self.normalized = normalized
        self._recs: List[tuple] = []
        self._H = np.zeros((5, num_bins + 2, 0))
        self._Z = np.zeros((num_bins, 0))
        self._edges: Optional[np.ndarray] = None

    def begin(self, stats) -> None:
        if stats.n_events == 0:
            return
        t0, t1 = stats.ts_min, stats.ts_max
        if t1 <= t0:
            t1 = t0 + 1.0
        self._edges = np.linspace(t0, t1, self.num_bins + 1)

    def update(self, chunk) -> None:
        calls = chunk.calls
        if calls is None or len(calls.name) == 0:
            return
        if self.backend != "numpy":
            w = np.nan_to_num(calls.inc if self.metric == INC else calls.exc)
            self._recs.append((calls.name.copy(), calls.proc.copy(),
                               calls.start.copy(), calls.end.copy(), w))
            return
        nf = len(chunk.names)
        self._H = grow_to(self._H, (5, self.num_bins + 2, nf))
        self._Z = grow_to(self._Z, (self.num_bins, nf))
        starts, ends = calls.start, calls.end
        inc = ends - starts
        w = np.nan_to_num(calls.inc if self.metric == INC else calls.exc)
        rate = np.where(inc > 0, w / np.maximum(inc, 1e-30), 0.0)
        codes = calls.name
        si = np.searchsorted(self._edges, starts, side="left")
        ei = np.searchsorted(self._edges, ends, side="left")
        np.add.at(self._H[0], (si, codes), rate)
        np.add.at(self._H[1], (ei, codes), rate)
        np.add.at(self._H[2], (si, codes), rate * starts)
        np.add.at(self._H[3], (ei, codes), rate * starts)
        np.add.at(self._H[4], (ei, codes), rate * (ends - starts))
        zsel = inc <= 0
        if np.any(zsel & (w > 0)):
            b = np.clip(np.searchsorted(self._edges, starts[zsel],
                                        side="right") - 1,
                        0, self.num_bins - 1)
            np.add.at(self._Z, (b, codes[zsel]), w[zsel])

    def merge_from(self, other, code_map) -> None:
        # bin edges come from the shared stats pre-pass, so workers and
        # parent agree on them; only the name axis needs remapping
        if self.backend != "numpy":
            for name, proc, start, end, w in other._recs:
                self._recs.append((code_map[name], proc, start, end, w))
            return
        self._H = _scatter_names(self._H, other._H, code_map, axis=2)
        self._Z = _scatter_names(self._Z, other._Z, code_map, axis=1)

    def result(self, ctx) -> EventFrame:
        if self._edges is None:
            return EventFrame({"bin_start": np.asarray([]),
                               "bin_end": np.asarray([])})
        nf = len(ctx.names)
        if self.backend != "numpy":
            names_alpha, _order, inv = _alpha(ctx, nf)
            if self._recs:
                name = np.concatenate([r[0] for r in self._recs])
                proc = np.concatenate([r[1] for r in self._recs])
                start = np.concatenate([r[2] for r in self._recs])
                end = np.concatenate([r[3] for r in self._recs])
                w = np.concatenate([r[4] for r in self._recs])
            else:
                name = proc = np.zeros(0, np.int64)
                start = end = w = np.zeros(0)
            return _profile_from_records(start, end, w, proc, inv[name],
                                         names_alpha, self._edges,
                                         self.num_bins, self.normalized,
                                         self._fn)
        H = _pad_to(self._H, (5, self.num_bins + 2, nf))
        Z = _pad_to(self._Z, (self.num_bins, nf))
        cum = np.cumsum(H[:, : self.num_bins + 1, :], axis=1)
        t = self._edges[:, None]
        C = t * (cum[0] - cum[1]) - (cum[2] - cum[3]) + cum[4]
        prof = np.maximum(np.diff(C, axis=0), 0.0) + Z
        names_alpha, order, _inv = _alpha(ctx, nf)
        prof = prof[:, order]
        if self.normalized:
            denom = prof.sum(axis=1, keepdims=True)
            prof = prof / np.maximum(denom, 1e-30)
        out = EventFrame({"bin_start": self._edges[:-1],
                          "bin_end": self._edges[1:]})
        keep = np.nonzero(prof.sum(axis=0) > 0)[0]
        order = keep[np.argsort(-prof[:, keep].sum(axis=0), kind="stable")]
        for f in order:
            out[str(names_alpha[f])] = prof[:, f]
        return out


@register_streaming("load_imbalance")
class _LoadImbalanceAgg(StreamAgg):
    """Combinable load imbalance: the per-(function, process) metric totals
    merge exactly across chunks (integer-ns sums); the ratio arithmetic at
    finalize is identical to the in-memory op.  ``backend="pallas"``
    buffers records and runs the pair_sum kernel once at finalize, exactly
    like the eager pallas backend."""

    needs_calls = True
    supports_parallel = True

    def __init__(self, metric: str = EXC, num_processes: int = 5,
                 top_functions: Optional[int] = None,
                 backend: str = "numpy"):
        _check_metric(metric, "load_imbalance")
        get_backend("load_imbalance", backend)
        if backend not in ("numpy", "pallas"):
            raise StreamingUnsupported(
                f"streaming load_imbalance supports backends ('numpy', "
                f"'pallas'); {backend!r} is trace-level — materialize with "
                f".collect() to use it")
        self.backend = backend
        self.metric = metric
        self.num_processes = num_processes
        self.top_functions = top_functions
        self._recs: List[tuple] = []
        self._tot = np.zeros((0, 0))

    def update(self, chunk) -> None:
        calls = chunk.calls
        if calls is None or len(calls.name) == 0:
            return
        vals = calls.inc if self.metric == INC else calls.exc
        if self.backend != "numpy":
            self._recs.append((calls.name.copy(), calls.proc.copy(),
                               calls.start.copy(), calls.end.copy(),
                               np.nan_to_num(vals)))
            return
        nf = len(chunk.names)
        np_ = int(calls.proc.max()) + 1
        self._tot = grow_to(self._tot, (nf, np_))
        np.add.at(self._tot, (calls.name, calls.proc), vals)

    def merge_from(self, other, code_map) -> None:
        if self.backend != "numpy":
            for name, proc, start, end, vals in other._recs:
                self._recs.append((code_map[name], proc, start, end, vals))
            return
        self._tot = _scatter_names(self._tot, other._tot, code_map, axis=0)

    def result(self, ctx) -> EventFrame:
        nf = len(ctx.names)
        nprocs = ctx.num_processes
        names_alpha, order, inv = _alpha(ctx, nf)
        if self.backend == "numpy":
            tot = _pad_to(self._tot, (nf, max(nprocs, 1)))[order]
        else:
            if self._recs:
                name = np.concatenate([r[0] for r in self._recs])
                proc = np.concatenate([r[1] for r in self._recs])
                start = np.concatenate([r[2] for r in self._recs])
                end = np.concatenate([r[3] for r in self._recs])
                vals = np.concatenate([r[4] for r in self._recs])
            else:
                name = proc = np.zeros(0, np.int64)
                start = end = vals = np.zeros(0)
            acode = inv[name]
            o = accel.canonical_order(start, end, proc, acode, vals)
            tot = accel.pair_sum(acode[o], proc[o], vals[o], nf,
                                 max(nprocs, 1))
        return _imbalance_assemble(tot, names_alpha, self.metric,
                                   self.num_processes, self.top_functions,
                                   nprocs)


@register_streaming("idle_time")
class _IdleTimeAgg(StreamAgg):
    """Combinable idle time: per-process inclusive-ns sums of idle-named
    completed calls — exact merge for integer-ns traces."""

    needs_calls = True
    supports_parallel = True

    def __init__(self, idle_functions: Sequence[str] = DEFAULT_IDLE_NAMES,
                 k: Optional[int] = None):
        self.idle = [str(n) for n in idle_functions]
        self.k = k
        self._out = np.zeros(0)

    def update(self, chunk) -> None:
        calls = chunk.calls
        if calls is None or len(calls.name) == 0:
            return
        idle_codes = [c for c in
                      (chunk.names.code(n) for n in self.idle)
                      if c >= 0]
        if not idle_codes:
            return
        sel = np.isin(calls.name, np.asarray(idle_codes, np.int64))
        if not np.any(sel):
            return
        np_ = int(calls.proc[sel].max()) + 1
        self._out = grow_to(self._out, (np_,))
        np.add.at(self._out, calls.proc[sel], np.nan_to_num(calls.inc[sel]))

    def merge_from(self, other, code_map) -> None:
        # keyed by process only (idle-name matching already happened in the
        # worker's own code space); plain padded add
        self._out = grow_to(self._out, other._out.shape)
        self._out[: len(other._out)] += other._out

    def result(self, ctx) -> EventFrame:
        nprocs = ctx.num_processes
        out = np.zeros(max(nprocs, 0))
        sub = self._out[:nprocs]
        out[: len(sub)] = sub
        order = np.argsort(-out, kind="stable")
        res = EventFrame({PROC: order.astype(np.int32),
                          "idle_time": out[order]})
        return res.head(self.k) if self.k else res


def multi_run_analysis(traces: Sequence, metric: str = EXC, top_n: int = 16,
                       label_column: str = "Run") -> EventFrame:
    """Joined flat profiles across runs (§IV-D, Fig. 12).

    Thin wrapper over the TraceDiff alignment machinery
    (:func:`repro.core.diff.align_flat_profiles`): one row per run, one
    column per function in the union of each run's top-``top_n`` functions
    by ``metric`` (columns ordered by total weight across runs).  For
    deltas, scaling series, or regression flags use the set-scoped ops in
    :mod:`repro.core.diff` directly.
    """
    from .diff import align_flat_profiles
    labels, cols, mat, _present = align_flat_profiles(traces, metric=metric,
                                                      top_n=top_n)
    out = EventFrame({label_column: np.asarray(labels, dtype=object)})
    for j, c in enumerate(cols):
        out[c] = mat[:, j]
    return out
