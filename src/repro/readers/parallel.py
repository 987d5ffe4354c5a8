"""Parallel reading driver (paper §VI, Fig. 5 center).

Trace archives are naturally sharded per location (OTF2 keeps one event
stream per rank; our JSONL traces can be split the same way).  This driver
fans a reader over shards with ``multiprocessing`` and concatenates the
resulting frames — the paper's strategy for scaling trace ingest with cores.

Format dispatch goes through the unified reader registry
(:mod:`repro.core.registry`), so ``kind="auto"`` sniffs each shard and any
user-registered format works here too.  When the caller (typically a lazy
query plan, see :mod:`repro.core.query`) restricts processes, shards whose
registered ``shard_procs`` hint proves they cannot contribute are *skipped
before parsing* — predicate pushdown into the reader.
"""

from __future__ import annotations

import os
from typing import Callable, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..core.constants import (DERIVED_COLUMNS, ENTER, ET, INSTANT, LEAVE,
                              NAME, PROC, TS)
from ..core.frame import Categorical, EventFrame, concat
from ..core.registry import ReaderSpec, resolve_reader
from ..core.trace import Trace
# spawn-safety rules and pool construction live in repro.parallel_util so
# every parallel driver (this reader, TraceSet preparation, the plan
# executor) shares one serial-fallback behavior; spawn_pool_ok is
# re-exported here because it is this module's historical public home
from ..parallel_util import map_maybe_parallel, spawn_pool_ok

__all__ = ["read_parallel", "open_many", "select_shards",
           "split_jsonl_by_process", "spawn_pool_ok"]


def _ensure_registered() -> None:
    # Importing the reader modules populates the registry.  Needed both in
    # the parent (when only this module was imported) and in spawned pool
    # workers, which start from a fresh interpreter.
    from . import chrome, csvreader, hlo, jsonl, otf2j  # noqa: F401


def _read_one(args) -> EventFrame:
    kind, path, reader_kwargs = args
    _ensure_registered()
    ev = resolve_reader(path, kind).read(path, **(reader_kwargs or {})).events
    # per-shard derived structure (pack sidecars) indexes the shard's own
    # rows; the merged sort below invalidates it — strip before concat
    return ev.drop(*DERIVED_COLUMNS)


def select_shards(paths: Sequence[str], kind: str = "auto",
                  procs: Optional[Set[int]] = None,
                  proc_bounds: Optional[Tuple[float, float]] = None,
                  resolve: Optional[Callable[[str], ReaderSpec]] = None
                  ) -> List[str]:
    """Shards that can contribute events under the given process restriction.

    A shard is kept when its reader provides no ``shard_procs`` hint (unknown
    contents are never skipped) or when any hinted process id satisfies both
    the explicit set and the [lo, hi] bounds.  ``resolve`` maps a path to
    its reader (a handle's remembered sniff); ``resolve_reader(p, kind)``
    by default.
    """
    paths = list(paths)
    if procs is None and proc_bounds is None:
        return paths
    _ensure_registered()
    keep: List[str] = []
    for p in paths:
        spec = resolve(p) if resolve is not None else resolve_reader(p, kind)
        hint = spec.shard_procs(p) if spec.shard_procs else None
        if hint is None:
            keep.append(p)
            continue
        if any((procs is None or q in procs)
               and (proc_bounds is None
                    or proc_bounds[0] <= q <= proc_bounds[1])
               for q in hint):
            keep.append(p)
    return keep


def read_parallel(paths: Sequence[str], kind: str = "auto",
                  processes: Optional[int] = None,
                  label: Optional[str] = None,
                  procs: Optional[Set[int]] = None,
                  proc_bounds: Optional[Tuple[float, float]] = None,
                  **reader_kwargs) -> Trace:
    """Read per-location shards in parallel and merge into one Trace.

    Extra keyword arguments are forwarded to every per-shard reader (e.g.
    ``n_procs=...`` for HLO shards).
    """
    _ensure_registered()
    sel = select_shards(paths, kind, procs=procs, proc_bounds=proc_bounds)
    if not sel:
        # canonical empty frame: analysis ops on a fully-pruned read must
        # see the uniform columns, not a column-less frame
        empty = EventFrame({
            TS: np.asarray([], np.int64),
            ET: Categorical.from_codes(np.asarray([], np.int32),
                                       np.asarray([ENTER, LEAVE, INSTANT])),
            NAME: Categorical.from_codes(np.asarray([], np.int32),
                                         np.asarray([], dtype=object)),
            PROC: np.asarray([], np.int64),
        })
        return Trace(empty, label=label or "parallel[0]")
    processes = processes or min(len(sel), os.cpu_count() or 1)
    args = [(kind, p, reader_kwargs) for p in sel]
    frames, _pooled = map_maybe_parallel(_read_one, args, processes)
    ev = concat(frames).sort_by([PROC, TS])
    return Trace(ev, label=label or f"parallel[{len(sel)}]")


def _open_one(args) -> Trace:
    kind, item, reader_kwargs = args
    _ensure_registered()
    return Trace.open(item, format=kind, **(reader_kwargs or {}))


def open_many(paths: Sequence, kind: str = "auto",
              processes: Optional[int] = None,
              **reader_kwargs) -> List[Trace]:
    """Open N *whole traces* (batched ingest for TraceSet / cross-run diffs).

    Unlike :func:`read_parallel`, which merges per-location shards of ONE
    trace, this returns one Trace per item.  Each item goes through the
    reader registry exactly like ``Trace.open`` (format sniffed per member
    when ``kind="auto"``) and may itself be a list of shard paths, which is
    read through the sharded driver.  ``processes`` > 1 opens members in a
    ``multiprocessing`` pool (spawn: the calling script needs the standard
    ``if __name__ == "__main__"`` guard); the default is serial, since
    members opened for comparison are often already in memory or small.
    """
    _ensure_registered()
    if isinstance(paths, (str, os.PathLike)):
        paths = [paths]  # a bare path must not be iterated char-by-char
    items = list(paths)
    args = [(kind, os.fspath(p) if isinstance(p, (str, os.PathLike)) else
             [os.fspath(q) for q in p], reader_kwargs) for p in items]
    if not args:
        return []
    traces, _pooled = map_maybe_parallel(_open_one, args, processes)
    return traces


def split_jsonl_by_process(path: str, out_dir: str) -> List[str]:
    """Shard a JSONL trace by process id (one file per rank)."""
    import json
    os.makedirs(out_dir, exist_ok=True)
    handles = {}
    try:
        with open(path) as f:
            for line in f:
                if not line.strip():
                    continue
                p = json.loads(line).get("proc", 0)
                if p not in handles:
                    handles[p] = open(os.path.join(out_dir, f"rank_{p}.jsonl"),
                                      "w")
                handles[p].write(line)
    finally:
        for h in handles.values():
            h.close()
    return [os.path.join(out_dir, f"rank_{p}.jsonl")
            for p in sorted(handles)]
