"""The comparison that decides ``correct``: each served result against the
plain reference, reduced to a few numbers, each held to its own limit.

* ``unanswered`` — requests that got no result (an error or a refusal).
* ``exact_mismatch`` — entries that must agree exactly and do not: counts,
  histogram bins and edges, the set of names or bins, flagged ranks, rank
  time bounds.
* ``sum_gap`` — the widest relative gap of a summed metric (flat profile
  sums, load-imbalance mean and max and the totals of the ranks it ranks
  first, comm-matrix bytes, straggler severity): ``|got - ref| / |ref|``
  per entry.  The program sums in float32; the reference sums exactly.
* ``profile_gap`` — the widest gap of a ``time_profile`` cell, over the
  largest cell of that profile: a call's share of a bin is computed from
  timestamps, so a nearly empty cell carries the rounding of its bin's
  edges, not of its own sum.
"""

from __future__ import annotations

import json
from typing import Dict

import numpy as np

NUMBERS = ("unanswered", "exact_mismatch", "sum_gap", "profile_gap")


class Tally:
    def __init__(self):
        self.values: Dict[str, float] = {k: 0 for k in NUMBERS}
        self.compared = 0

    # -- accumulation ------------------------------------------------------
    def unanswered(self, n: int = 1) -> None:
        self.values["unanswered"] += n

    def exact(self, got, ref) -> None:
        got, ref = np.asarray(got), np.asarray(ref)
        if got.shape != ref.shape:
            self.values["exact_mismatch"] += max(got.size, ref.size, 1)
            return
        self.values["exact_mismatch"] += int(np.count_nonzero(got != ref))

    def sums(self, got, ref) -> None:
        got = np.asarray(got, np.float64)
        ref = np.asarray(ref, np.float64)
        if got.shape != ref.shape:
            self.values["exact_mismatch"] += max(got.size, ref.size, 1)
            return
        if not ref.size:
            return
        scale = max(float(np.abs(ref).max()), 1.0)
        den = np.where(ref != 0, np.abs(ref), scale)
        self._worst("sum_gap", np.abs(got - ref) / den)

    def profile(self, got, ref) -> None:
        got = np.asarray(got, np.float64)
        ref = np.asarray(ref, np.float64)
        if not ref.size:
            return
        scale = max(float(np.abs(ref).max()), 1.0)
        self._worst("profile_gap", np.abs(got - ref) / scale)

    def _worst(self, key: str, gaps: np.ndarray) -> None:
        if gaps.size:
            g = float(np.nanmax(np.where(np.isnan(gaps), np.inf, gaps)))
            self.values[key] = max(self.values[key], g)

    # -- per op ------------------------------------------------------------
    def compare(self, op: str, got, ref) -> None:
        """Compare one served result with its reference (the op's module
        under ``bench/ops``)."""
        from . import ops
        self.compared += 1
        ops.load(op).compare(self, got, ref)

    def names(self, g: Dict[str, int], r: Dict[str, int]) -> None:
        """Names on one side and not the other."""
        self.values["exact_mismatch"] += len(set(g) ^ set(r))

    # -- verdict -----------------------------------------------------------
    def verdict(self, limits: Dict[str, float]
                ) -> Dict[str, Dict[str, float]]:
        """Each number the cell's limits file names, beside its limit."""
        return {k: {"value": self.values[k], "limit": limits[k]}
                for k in NUMBERS if k in limits}


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def rows_by_name(frame, col: str) -> Dict[str, int]:
    if len(frame) == 0:
        return {}
    names = frame[col]
    if hasattr(names, "categories"):
        names = np.asarray(names.categories)[np.asarray(names.codes)]
    return {str(n): i for i, n in enumerate(np.asarray(names))}


def limits_for(path: str) -> Dict[str, float]:
    """The ``limits`` of a cell's ``bench/limits/<workload>.json``."""
    with open(path) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}
