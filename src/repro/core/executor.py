"""Multi-core execution of streaming TraceQuery plans (paper §VI scaled out).

The out-of-core engine (:mod:`repro.core.streaming`) runs a fused plan mask
plus a combinable aggregator chunk by chunk — serially, in one Python
process, leaving every other core idle on multi-GB traces.  This module is
the parallel driver on top of the *same* plan machinery:

* **unit planning** — the input is partitioned into independent work units
  in stream order: whole shard paths, byte ranges of line-oriented files
  (:class:`~repro.core.registry.ByteSpan`, planned by the format's
  registered ``plan_units``), or process subsets
  (:class:`~repro.core.registry.ProcSpan`, enforced with an explicit mask
  — reader hints stay advisory);
* **worker fold** — each unit runs the identical serial pipeline (pushdown
  hints → fused mask per chunk → streaming aggregator), with the
  :class:`~repro.core.streaming.CallStitcher` in *deferred* mode: events a
  unit cannot resolve locally (a Leave whose Enter lives in an earlier
  unit, call time owed to a call opened upstream) are recorded as **seam
  events** instead of being dropped;
* **merge** — the parent interns worker name tables in unit order
  (reproducing the serial first-seen code space), folds each worker's
  partial aggregate in through the op's declared
  :meth:`~repro.core.streaming.StreamAgg.merge_from`, and replays the seam
  events against the carry stacks of the preceding units — so enter/leave
  pairs split across unit seams complete with exactly the inclusive /
  exclusive attribution the serial stitcher produces.

Because every partial is a sum of integer-ns (or integer-count) values,
merge order cannot change a bit: results are byte-identical to serial
streaming for all exactly-combinable ops (``time_profile`` agrees to
float64 rounding, the same caveat it already carries vs eager execution).

Degradations back to serial streaming raise :class:`ParallelDegraded`
internally; ``execute_streaming`` converts that into a warning naming the
concrete reason (non-mergeable op, spawn-unsafe ``__main__``, nothing to
fan out, unsplittable input).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from . import registry
from .constants import ENTER, ET, INSTANT, LEAVE, NAME, PROC, TS
from .frame import Categorical, EventFrame
from .streaming import (CallBlock, CallStitcher, Chunk, GlobalNames,
                        StreamAgg, StreamContext, StreamStats,
                        StreamingUnsupported, _steps_hints, finalize,
                        fold_frames, iter_chunks_fallback, mask_frames,
                        stats_from_frames)
from ..parallel_util import resolve_processes, spawn_unsafe_reason

__all__ = ["execute_parallel", "plan_units", "ParallelDegraded"]


class ParallelDegraded(RuntimeError):
    """Parallel execution is not applicable; fall back to serial streaming.
    The message is the user-facing reason (it ends up in a warning)."""


# ---------------------------------------------------------------------------
# unit planning
# ---------------------------------------------------------------------------

def _path_bytes(path: str) -> int:
    if os.path.isdir(path):
        total = 0
        for root, _dirs, files in os.walk(path):
            for f in files:
                try:
                    total += os.path.getsize(os.path.join(root, f))
                except OSError:  # pragma: no cover - racing deletes
                    pass
        return total
    try:
        return os.path.getsize(path)
    except OSError:
        return 0


def plan_units(handle, steps: Sequence, n_workers: int) -> List[Any]:
    """Partition the handle's (shard-skipped) input into work units.

    Units come back in stream order — path order, byte spans in offset
    order — which is what makes cross-unit seam replay equivalent to the
    serial chunk sequence.  A unit is a whole path (str), a
    :class:`~repro.core.registry.ByteSpan`, or a
    :class:`~repro.core.registry.ProcSpan`.

    Plans are memoized on the handle per (selected paths + their stat,
    n_workers): planners can be expensive (chrome's pid pre-pass decodes
    the stream), and every terminal op re-plans otherwise.  The per-path
    (size, mtime_ns) in the key means a file that grows between ops
    re-plans — byte spans computed against the old extent would silently
    truncate it.
    """
    import os as _os
    from .. import readers  # noqa: F401 — populate the registry
    from ..readers.parallel import select_shards
    hints = _steps_hints(steps)
    procs = set(hints.procs) if hints.procs is not None else None
    paths = select_shards(handle.paths, handle.format, procs=procs,
                          proc_bounds=hints.proc_bounds)
    if not paths:
        return []

    def _stat(p):
        # directories (otf2j archives) must reflect in-place rewrites of
        # contained files — the dir's own mtime only tracks entry add/remove
        try:
            if _os.path.isdir(p):
                size = mtime = n = 0
                for root, _dirs, files in _os.walk(p):
                    for fn in files:
                        st = _os.stat(_os.path.join(root, fn))
                        size += st.st_size
                        mtime = max(mtime, st.st_mtime_ns)
                        n += 1
                return (size, mtime, n)
            st = _os.stat(p)
            return (st.st_size, st.st_mtime_ns)
        except OSError:
            return (-1, -1)

    cache = getattr(handle, "_units_cache", None)
    if cache is None:
        cache = handle._units_cache = {}
    cache_key = (tuple((p,) + _stat(p) for p in paths), n_workers)
    if cache_key in cache:
        return cache[cache_key]
    sizes = [_path_bytes(p) for p in paths]
    total = max(sum(sizes), 1)
    units: List[Any] = []
    planner = getattr(handle, "plan_units_for", None)
    for p, sz in zip(paths, sizes):
        # shares of the worker budget proportional to file size
        want = max(1, round(sz * n_workers / total))
        if planner is not None:
            # handle-owned planning (live handles): the units it returns
            # are authoritative even when there is only one — a whole-path
            # unit would read past the pinned snapshot watermark
            units.extend(planner(p, want))
            continue
        spec = registry.resolve_reader(p, handle.format)
        sub = None
        if want > 1 and spec.plan_units is not None:
            sub = spec.plan_units(p, want)
        if sub and len(sub) > 1:
            units.extend(sub)
        else:
            units.append(p)
    cache[cache_key] = units
    return units


# ---------------------------------------------------------------------------
# worker side
# ---------------------------------------------------------------------------

def _unit_frames(unit, fmt: str, chunk_rows: int,
                 hints: Optional[registry.PlanHints],
                 reader_kwargs: dict) -> Iterator[EventFrame]:
    """Raw chunk frames of one work unit (pushdown hints applied)."""
    if isinstance(unit, registry.ByteSpan):
        spec = registry.resolve_reader(unit.path, fmt)
        yield from spec.iter_chunks(unit.path, chunk_rows, hints,
                                    byte_range=(unit.lo, unit.hi),
                                    **reader_kwargs)
        return
    if isinstance(unit, registry.RowSpan):
        # random-access columnar unit (pack): the reader slices rows
        # directly, no boundary alignment needed
        spec = registry.resolve_reader(unit.path, fmt)
        yield from spec.iter_chunks(unit.path, chunk_rows, hints,
                                    row_range=(unit.lo, unit.hi),
                                    **reader_kwargs)
        return
    if isinstance(unit, registry.ProcSpan):
        spec = registry.resolve_reader(unit.path, fmt)
        pset = frozenset(unit.procs)
        if hints is not None and hints.procs is not None:
            pset = pset & hints.procs
        sub = registry.PlanHints(
            procs=pset,
            proc_bounds=hints.proc_bounds if hints else None,
            time_window=hints.time_window if hints else None)
        kw = dict(unit.extra)
        kw.update(reader_kwargs)
        parr = np.asarray(sorted(pset), np.int64)
        for frame in spec.iter_chunks(unit.path, chunk_rows, sub, **kw):
            # hints are advisory; the unit's process subset is a partition
            # contract, so enforce it here
            m = np.isin(np.asarray(frame[PROC], np.int64), parr)
            yield frame if m.all() else frame.mask(m)
        return
    spec = registry.resolve_reader(unit, fmt)
    if spec.iter_chunks is not None:
        yield from spec.iter_chunks(unit, chunk_rows, hints, **reader_kwargs)
    else:
        yield from iter_chunks_fallback(unit, chunk_rows, hints, spec.read,
                                        **reader_kwargs)


class _UnitResult:
    """What one worker sends back: its name table (first-seen order), the
    updated aggregator, and — for call-stitching ops — the seam events,
    trailing open frames, and per-group time span."""

    __slots__ = ("names", "agg", "proc_max", "seams", "trailing",
                 "first_ts", "last_ts")

    def __init__(self, names, agg, proc_max, seams, trailing, first_ts,
                 last_ts):
        self.names = names
        self.agg = agg
        self.proc_max = proc_max
        self.seams = seams
        self.trailing = trailing
        self.first_ts = first_ts
        self.last_ts = last_ts

    def __getstate__(self):
        return {s: getattr(self, s) for s in self.__slots__}

    def __setstate__(self, state):
        for s in self.__slots__:
            setattr(self, s, state[s])


def _run_unit(payload) -> Any:
    """Pool worker: run one unit through the serial streaming pipeline.

    ``mode="stats"`` folds the unit into a StreamStats partial;
    ``mode="fold"`` builds the op's aggregator, feeds the unit's masked
    chunks through a deferring CallStitcher, and returns a _UnitResult.
    """
    (mode, unit, fmt, chunk_rows, reader_kwargs, steps, factory, args,
     kwargs, stats, label) = payload
    from ..readers import parallel as _rp
    _rp._ensure_registered()
    hints = _steps_hints(steps)
    frames = mask_frames(
        _unit_frames(unit, fmt, chunk_rows, hints, reader_kwargs),
        steps, label)
    if mode == "stats":
        return stats_from_frames(frames)
    agg: StreamAgg = factory(*args, **kwargs)
    agg.begin(stats)
    names = GlobalNames()
    stitcher = CallStitcher(defer_unmatched=True) if agg.needs_calls else None
    proc_max = fold_frames(frames, agg, names, stitcher)
    if stitcher is not None:
        first_ts, last_ts = stitcher.group_span()
        return _UnitResult(names.names, agg, proc_max, stitcher.seams(),
                           stitcher.trailing(), first_ts, last_ts)
    return _UnitResult(names.names, agg, proc_max, {}, {}, {}, {})


# ---------------------------------------------------------------------------
# parent side: merge
# ---------------------------------------------------------------------------

def _empty_events() -> EventFrame:
    """Canonical zero-row frame (uniform columns) carrying seam-completed
    calls into an aggregator update."""
    return EventFrame({
        TS: np.asarray([], np.int64),
        ET: Categorical.from_codes(np.asarray([], np.int32),
                                   np.asarray([ENTER, LEAVE, INSTANT])),
        NAME: Categorical.from_codes(np.asarray([], np.int32),
                                     np.asarray([], dtype=object)),
        PROC: np.asarray([], np.int64),
    })


def _merge_results(agg: StreamAgg, stats: Optional[StreamStats],
                   results: Sequence[_UnitResult]) -> Any:
    """Fold worker results, in unit order, into one finalized op result."""
    names = GlobalNames()
    agg.begin(stats)
    proc_max = -1
    # per-group carry stacks across unit seams: [name, proc, start, child_inc]
    prefix: Dict[int, List[list]] = {}
    last_ts: Dict[int, float] = {}
    for r in results:
        code_map = np.asarray([names.intern(str(s)) for s in r.names],
                              np.int64)
        for g, ft in r.first_ts.items():
            lt = last_ts.get(g)
            if lt is not None and ft < lt:
                raise StreamingUnsupported(
                    "streaming execution needs each (process, thread) event "
                    "stream in non-decreasing time order across parallel "
                    "work units; this trace interleaves out of order.  "
                    "Re-shard it or open with streaming=False.")
        for g, lt in r.last_ts.items():
            if lt > last_ts.get(g, -np.inf):
                last_ts[g] = lt
        # replay this unit's seam events against the upstream carry stacks
        completed: List[tuple] = []
        for g, items in r.seams.items():
            stack = prefix.setdefault(g, [])
            for item in items:
                if item[0] == "a":
                    if stack:
                        stack[-1][3] += item[1]
                    # no open call upstream: the serial stitcher drops the
                    # attribution too
                else:
                    _tag, ts_, _proc = item
                    if stack:
                        nm, pc, st_, ci = stack.pop()
                        inc = ts_ - st_
                        completed.append((nm, pc, st_, ts_, inc, inc - ci))
                        if stack:
                            stack[-1][3] += inc
                    # else: Leave with no open call anywhere — unmatched in
                    # the serial path as well; ignore
        # trailing open frames stack on top for the next units (name codes
        # remapped into the merged space now, so later pops need no map)
        for g, frames_ in r.trailing.items():
            stack = prefix.setdefault(g, [])
            for nm, pc, st_ts, ci in frames_:
                stack.append([int(code_map[nm]), int(pc), float(st_ts),
                              float(ci)])
        agg.merge_from(r.agg, code_map)
        if completed:
            cn, cp, cs, ce, ci_, cx = (np.asarray(c)
                                       for c in zip(*completed))
            block = CallBlock(cn.astype(np.int64), cp.astype(np.int64),
                              cs.astype(np.float64), ce.astype(np.float64),
                              ci_.astype(np.float64), cx.astype(np.float64))
            agg.update(Chunk(_empty_events(), np.empty(0, np.int64), block,
                             names))
        proc_max = max(proc_max, r.proc_max)
    open_frames = [f for st in prefix.values() for f in st]
    open_calls = (np.asarray([f[0] for f in open_frames], np.int64),
                  np.asarray([f[1] for f in open_frames], np.int64))
    ctx = StreamContext(names, stats, open_calls, proc_max)
    return finalize(agg, ctx)


def _prune_units(units: List[Any], hints: registry.PlanHints) -> List[Any]:
    """Drop ProcSpan units whose process set the plan's restriction can
    never admit — their workers would decode the whole stream just to mask
    every row away.  Safe because ProcSpan sets partition the rows: a
    dropped unit contributes nothing under the plan mask."""
    if hints.procs is None and hints.proc_bounds is None:
        return units
    return [u for u in units
            if not isinstance(u, registry.ProcSpan)
            or any(hints.admits_proc(p) for p in u.procs)]


def parallel_stats(handle, steps: Sequence) -> StreamStats:
    """Run the StreamStats pre-pass over work units in the handle's pool.

    Raises :class:`ParallelDegraded` when fan-out is not applicable — the
    caller (``StreamingTrace.stats``) silently falls back to the serial
    pass, since a stats pass has no user-facing mode choice to warn about.
    """
    n = resolve_processes(handle.processes)
    if n <= 1:
        raise ParallelDegraded("processes=1 leaves nothing to fan out")
    units = _prune_units(plan_units(handle, steps, n), _steps_hints(steps))
    if len(units) <= 1:
        raise ParallelDegraded("input cannot be partitioned")
    reason = spawn_unsafe_reason()
    if reason is not None:
        raise ParallelDegraded(reason)
    if handle._pool is None:
        from .scheduler import get_scheduler
        handle._pool = get_scheduler().spawn_pool(n)
    payloads = [("stats", u, handle.format, handle.chunk_rows,
                 handle.reader_kwargs, tuple(steps), None, (), {}, None,
                 handle.label) for u in units]
    stats = StreamStats()
    for part in handle._pool.map(_run_unit, payloads):
        stats.merge(part)
    return stats


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def execute_parallel(handle, steps: Sequence, spec: registry.OpSpec,
                     args: tuple, kwargs: dict, agg: StreamAgg,
                     n_units: Optional[int] = None,
                     use_pool: bool = True) -> Any:
    """Fan one streaming op over work units and merge the partials.

    Raises :class:`ParallelDegraded` (with the user-facing reason) whenever
    multi-core execution is not applicable; the caller falls back to the
    serial path and warns.  ``n_units``/``use_pool`` exist for tests: they
    force a unit count and run workers in-process, exercising the seam
    machinery without pool startup cost.
    """
    if not getattr(agg, "supports_parallel", False):
        raise ParallelDegraded(
            f"op {spec.name!r} has a streaming form but no cross-worker "
            f"merge declaration (aggregator {type(agg).__name__}); it runs "
            f"serially")
    n = resolve_processes(handle.processes)
    if use_pool and n <= 1:
        raise ParallelDegraded("processes=1 leaves nothing to fan out")
    units = _prune_units(plan_units(handle, steps, n_units or n),
                         _steps_hints(steps))
    if len(units) <= 1:
        raise ParallelDegraded(
            "the input cannot be partitioned into more than one work unit "
            "(single file with no registered unit planner, or everything "
            "was pruned by shard skipping / the plan's process "
            "restriction)")
    if use_pool:
        reason = spawn_unsafe_reason()
        if reason is not None:
            raise ParallelDegraded(reason)
        if handle._pool is None:
            # pool ownership lives in the shared scheduler: every handle
            # (and every trace-query service session) asking for n workers
            # fans into the same spawn pool, so worker startup is paid once
            # per process, not once per handle
            from .scheduler import get_scheduler
            handle._pool = get_scheduler().spawn_pool(n)
        try:
            handle._pool.get()
        except RuntimeError as e:  # pragma: no cover - raced __main__ state
            raise ParallelDegraded(str(e)) from None
        mapper = lambda payloads: handle._pool.map(_run_unit, payloads)  # noqa: E731
    else:
        mapper = lambda payloads: [_run_unit(p) for p in payloads]  # noqa: E731

    def payload(mode, unit, stats=None):
        return (mode, unit, handle.format, handle.chunk_rows,
                handle.reader_kwargs, tuple(steps), spec.streaming, args,
                kwargs, stats, handle.label)

    stats = None
    if agg.needs_stats:
        if tuple(steps) == tuple(handle._steps) and handle._stats0 is not None:
            stats = handle._stats0
        else:
            stats = StreamStats()
            for part in mapper([payload("stats", u) for u in units]):
                stats.merge(part)
            if tuple(steps) == tuple(handle._steps):
                handle._stats0 = stats
    results = mapper([payload("fold", u, stats) for u in units])
    return _merge_results(agg, stats, results)
