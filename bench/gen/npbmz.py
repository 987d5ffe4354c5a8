"""Ranks of an NPB Multi-Zone run (SP-MZ, BT-MZ): one zone per MPI rank on
an ``x_zones`` x ``y_zones`` torus of zones, ``niter`` time steps.

The configuration's ``step`` is the call tree of one time step: a list of
``{"call": name, "calls": [...], "repeat": k, "sends": true}`` entries.  A
call's exclusive work is lognormal around its ``exc_us`` median ``[median
us, sigma]``; every enter and leave row also advances the rank's clock by
``gap_ns``.  The ``k``-th call marked ``sends`` in a step sends to the
rank's ``k``-th zone neighbour (west, east, south, north), with a message
instant at its middle.  A message carries one face of the zone: ``nvars``
values of ``word_bytes`` over the face's points.  ``main`` spans the rank.

Only durations and the rank's start come from ``--seed``: every seed gives
the same rows, names, partners and sizes.
"""

from __future__ import annotations

import functools
import json
from typing import Dict, List, Tuple

import numpy as np

from .tracegen import ENTER, INSTANT, LEAVE, Rank

ROOT_NAME = "main"
SEND_NAME = "MpiSend"


@functools.lru_cache(maxsize=8)
def _template(step_json: str) -> Tuple[List[Tuple[str, int, int]],
                                       List[Tuple[int, int]]]:
    """Calls ``(name, depth, send index or -1)`` in preorder, and the rows
    ``(kind, call)`` of one step in time order."""
    calls: List[Tuple[str, int, int]] = []
    rows: List[Tuple[int, int]] = []
    sends = 0

    def walk(entries, depth):
        nonlocal sends
        for e in entries:
            for _ in range(int(e.get("repeat", 1))):
                c = len(calls)
                k = -1
                if e.get("sends"):
                    k, sends = sends, sends + 1
                calls.append((e["call"], depth, k))
                rows.append((ENTER, c))
                if k >= 0:
                    rows.append((INSTANT, c))
                walk(e.get("calls", []), depth + 1)
                rows.append((LEAVE, c))

    walk(json.loads(step_json), 1)
    if sends != 4:
        raise ValueError(f"a step sends {sends} messages; a zone has 4 "
                         "neighbours")
    return calls, rows


def _step(cfg: Dict):
    return _template(json.dumps(cfg["step"], sort_keys=True))


def names(cfg: Dict) -> List[str]:
    out = [ROOT_NAME]
    for name, _, _ in _step(cfg)[0]:
        if name not in out:
            out.append(name)
    return out + [SEND_NAME]


def neighbours(cfg: Dict, p: int) -> np.ndarray:
    """West, east, south and north zone neighbours of rank ``p``."""
    x, y = int(cfg["x_zones"]), int(cfg["y_zones"])
    ix, iy = p % x, p // x
    return np.asarray([iy * x + (ix - 1) % x, iy * x + (ix + 1) % x,
                       ((iy - 1) % y) * x + ix, ((iy + 1) % y) * x + ix],
                      np.int64)


def face_bytes(cfg: Dict) -> np.ndarray:
    """Bytes of the west, east, south and north faces of a zone."""
    nx = int(cfg["gx_size"]) // int(cfg["x_zones"])
    ny = int(cfg["gy_size"]) // int(cfg["y_zones"])
    per = int(cfg["nvars"]) * int(cfg["word_bytes"]) * int(cfg["gz_size"])
    return np.asarray([per * ny, per * ny, per * nx, per * nx], np.int64)


def rank(cfg: Dict, seed: int, p: int) -> Rank:
    if int(cfg["ranks"]) != int(cfg["x_zones"]) * int(cfg["y_zones"]):
        raise ValueError("one zone per rank: ranks must be x_zones*y_zones")
    calls, rows = _step(cfg)
    code = {n: i for i, n in enumerate(names(cfg))}
    T, C, R = int(cfg["niter"]), len(calls), len(rows)
    rng = np.random.default_rng([int(seed), p])

    med = np.asarray([cfg["exc_us"][n][0] for n, _, _ in calls]) * 1e3
    sig = np.asarray([cfg["exc_us"][n][1] for n, _, _ in calls])
    work = np.maximum(med * np.exp(sig * rng.standard_normal((T, C))),
                      100).astype(np.int64)

    kind = np.asarray([k for k, _ in rows], np.int64)
    rc = np.asarray([c for _, c in rows], np.int64)
    g0, g1 = cfg["gap_ns"]
    inc = rng.integers(g0, g1, (T, R))
    inc[:, kind == INSTANT] = 0
    # a call works right after its enter row; a send's work is split
    # around its message instant
    after_enter = np.nonzero(kind == ENTER)[0] + 1
    send = np.asarray([k >= 0 for _, _, k in calls])
    half = work // 2
    inc[:, after_enter] += np.where(send, half, work)
    leave_of_send = np.nonzero((kind == LEAVE) & send[rc])[0]
    inc[:, leave_of_send] += (work - half)[:, send]

    lo, hi = cfg["rank_start_ns"]
    t0 = int(rng.integers(lo, hi))
    clock = t0 + np.cumsum(inc.ravel())
    t_end = int(clock[-1]) + int(g0)

    is_msg = np.tile(kind == INSTANT, T)
    send_k = np.asarray([calls[c][2] for c in rc], np.int64)
    partner = np.where(kind == INSTANT, neighbours(cfg, p)[send_k], -1)
    size = np.where(kind == INSTANT, face_bytes(cfg)[send_k], 0)
    name = np.where(kind == INSTANT, code[SEND_NAME],
                    np.asarray([code[calls[c][0]] for c in rc]))
    depth = np.where(kind == INSTANT, 0,
                     np.asarray([calls[c][1] for c in rc]))
    tag = np.repeat(np.arange(T, dtype=np.int64), R)

    def framed(step_rows, root):
        return np.concatenate([[root], np.tile(step_rows, T), [root]])

    return Rank(
        ts=np.concatenate([[t0], clock, [t_end]]).astype(np.int64),
        et=np.concatenate([[ENTER], np.tile(kind, T), [LEAVE]]
                          ).astype(np.int8),
        name=framed(name, code[ROOT_NAME]).astype(np.int64),
        size=np.concatenate([[np.nan], np.where(is_msg,
                                                np.tile(size, T), np.nan),
                             [np.nan]]),
        partner=framed(partner, -1).astype(np.int64),
        tag=np.concatenate([[0], np.where(is_msg, tag, 0), [0]]),
        depth=framed(depth, 0).astype(np.int64))
