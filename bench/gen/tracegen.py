"""Seeded pack traces for the benchmark's deployments, with the ground truth
that the reference reads.

A configuration file under ``bench/configs`` names its ``shape``: the
module ``bench/gen/<shape>.py`` that draws one rank's rows.  Such a module
has ``names(cfg)`` (the name table) and ``rank(cfg, seed, p)`` (a
:class:`Rank`).  This module writes the ranks' pack shards and takes the
ground truth from the generated rows, never from what is read back:

* every call ``(proc, name, start, end, exc)``, from the enter and leave
  rows paired by depth;
* every message ``(src, dst, ts, size)``, from the send instants.

The pack shards are written with the program's own ``PackWriter`` (the
format is part of the system under test).
"""

from __future__ import annotations

import importlib
import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

ENTER, LEAVE, INSTANT = 0, 1, 2
_ET_STR = ("Enter", "Leave", "Instant")


@dataclass
class Truth:
    """Ground truth of one generated trace; calls sorted by start."""

    names: np.ndarray        # code -> name (str)
    ranks: int
    n_events: int
    call_proc: np.ndarray    # int64
    call_name: np.ndarray    # int64 code into ``names``
    call_start: np.ndarray   # int64 ns
    call_end: np.ndarray     # int64 ns
    call_exc: np.ndarray     # int64 ns (inclusive minus direct children)
    msg_proc: np.ndarray     # int64 sender
    msg_partner: np.ndarray  # int64 receiver
    msg_ts: np.ndarray       # int64 ns
    msg_size: np.ndarray     # int64 bytes
    rank_first: np.ndarray   # int64 first event ts per rank
    rank_last: np.ndarray    # int64 last event ts per rank


@dataclass
class Rank:
    """One rank's rows in time order."""

    ts: np.ndarray           # int64 ns
    et: np.ndarray           # ENTER, LEAVE or INSTANT
    name: np.ndarray         # int64 code into the name table
    size: np.ndarray         # float, NaN off messages
    partner: np.ndarray      # -1 off messages
    tag: np.ndarray
    depth: np.ndarray        # call depth of enter/leave rows (0 = root)


def shape(cfg: Dict):
    """The module that draws rows of the configuration's shape."""
    return importlib.import_module(f"{__package__}.{cfg['shape']}")


def generate(cfg: Dict, seed: int, out_dir: Optional[str]
             ) -> Tuple[List[str], Truth]:
    """Write ``cfg["ranks"]`` pack shards for ``seed`` into ``out_dir``;
    return their paths and the trace's ground truth.  With ``out_dir``
    None, only the ground truth is made."""
    gen = shape(cfg)
    names = np.asarray(gen.names(cfg))
    if out_dir is not None:
        os.makedirs(out_dir, exist_ok=True)
    paths, parts = [], []
    for p in range(int(cfg["ranks"])):
        r = gen.rank(cfg, seed, p)
        if out_dir is not None:
            path = os.path.join(out_dir, f"rank_{p}.pack")
            _write_pack(path, p, r, names,
                        int(cfg.get("chunk_rows", 250_000)))
            paths.append(path)
        parts.append(_rank_truth(p, r))
    return paths, _merge_truth(names, parts)


# ---------------------------------------------------------------------------
# shared: rows from calls, ground truth from rows, pack writing
# ---------------------------------------------------------------------------

def _rank_truth(p: int, r: Rank) -> Dict[str, np.ndarray]:
    """Calls (paired enter/leave rows) and messages of one rank.  A leave
    closes the latest open enter at its depth; exclusive time subtracts the
    inclusive time of the calls one level deeper that it encloses."""
    calls = np.nonzero(r.et != INSTANT)[0]
    by_depth = calls[np.lexsort((calls, r.depth[calls]))]
    leave = r.et[by_depth] == LEAVE
    li = np.nonzero(leave)[0]
    ent, lv = by_depth[li - 1], by_depth[li]
    if np.any(r.et[ent] != ENTER) or np.any(r.name[ent] != r.name[lv]):
        raise AssertionError("generator produced unbalanced call rows")
    start, end = r.ts[ent], r.ts[lv]
    dep = r.depth[ent]
    order = np.argsort(start, kind="stable")
    start, end, dep, name = start[order], end[order], dep[order], \
        r.name[ent][order]
    exc = end - start
    for dd in range(1, int(dep.max()) + 1 if dep.size else 0):
        child = np.nonzero(dep == dd)[0]
        par = np.nonzero(dep == dd - 1)[0]
        if not child.size:
            continue
        k = np.searchsorted(start[par], start[child], side="right") - 1
        np.subtract.at(exc, par[k], end[child] - start[child])
    msg = np.nonzero(r.et == INSTANT)[0]
    return {"proc": np.full(start.size, p, np.int64), "name": name,
            "start": start, "end": end, "exc": exc,
            "m_proc": np.full(msg.size, p, np.int64),
            "m_partner": r.partner[msg], "m_ts": r.ts[msg],
            "m_size": r.size[msg].astype(np.int64),
            "first": int(r.ts[0]), "last": int(r.ts[-1]), "rows": r.ts.size}


def _merge_truth(names: np.ndarray, parts: List[Dict]) -> Truth:
    cat = {k: np.concatenate([q[k] for q in parts])
           for k in ("proc", "name", "start", "end", "exc", "m_proc",
                     "m_partner", "m_ts", "m_size")}
    o = np.argsort(cat["start"], kind="stable")
    mo = np.argsort(cat["m_ts"], kind="stable")
    return Truth(
        names=names, ranks=len(parts),
        n_events=int(sum(q["rows"] for q in parts)),
        call_proc=cat["proc"][o], call_name=cat["name"][o],
        call_start=cat["start"][o], call_end=cat["end"][o],
        call_exc=cat["exc"][o], msg_proc=cat["m_proc"][mo],
        msg_partner=cat["m_partner"][mo], msg_ts=cat["m_ts"][mo],
        msg_size=cat["m_size"][mo],
        rank_first=np.asarray([q["first"] for q in parts], np.int64),
        rank_last=np.asarray([q["last"] for q in parts], np.int64))


def _write_pack(path: str, p: int, r: Rank, names: np.ndarray,
                chunk_rows: int) -> None:
    from repro.core.constants import (ET, MSG_SIZE, NAME, PARTNER, PROC, TAG,
                                      TS)
    from repro.core.frame import Categorical, EventFrame
    from repro.readers.pack import PackWriter
    with PackWriter(path, chunk_rows=chunk_rows) as w:
        w.append(EventFrame({
            TS: r.ts,
            ET: Categorical(r.et.astype(np.int32), np.asarray(_ET_STR)),
            NAME: Categorical(r.name.astype(np.int32), names),
            PROC: np.full(r.ts.size, p, np.int64),
            MSG_SIZE: r.size,
            PARTNER: r.partner,
            TAG: np.where(r.partner >= 0, r.tag, 0),
        }))
        w.finish(sidecar=True)
