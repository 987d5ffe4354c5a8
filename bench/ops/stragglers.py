"""``stragglers``: per rank, the ``time.exc`` of its computation (calls the
program does not class as communication); ranks ``threshold`` or more above
the mean, with their severity and first and last timestamps."""

import numpy as np

from ..selection import is_comm


def reference(s, threshold=0.2):
    comm = np.asarray([is_comm(str(n)) for n in s.names], bool)
    keep = ~comm[s.name]
    work = np.bincount(s.proc[keep], weights=s.exc[keep].astype(np.float64),
                       minlength=s.nprocs)
    mean = work.sum() / max(s.nprocs, 1)
    sev = (work - mean) / mean if mean > 0 else np.zeros_like(work)
    flagged = np.nonzero(sev >= threshold)[0] if mean > 0 else \
        np.zeros(0, np.int64)
    return {"process": flagged, "severity": sev[flagged], "all_severity": sev,
            "threshold": threshold,
            "t_start": s.rank_first[flagged].astype(np.float64),
            "t_end": s.rank_last[flagged].astype(np.float64)}


def compare(tally, got, ref):
    procs = np.asarray(got["process"], np.int64) if len(got) else \
        np.zeros(0, np.int64)
    sev = np.asarray(got["severity"], np.float64) if len(got) else \
        np.zeros(0)
    # a rank within rounding of the threshold may fall either side
    near = np.abs(ref["all_severity"] - ref["threshold"]) < 1e-4
    want = set(map(int, ref["process"]))
    have = set(map(int, procs))
    tally.values["exact_mismatch"] += sum(
        1 for p in want ^ have if not (0 <= p < near.size and near[p]))
    common = sorted(want & have)
    if common:
        gi = [int(np.nonzero(procs == p)[0][0]) for p in common]
        ri = [int(np.nonzero(ref["process"] == p)[0][0]) for p in common]
        tally.sums(sev[gi], ref["severity"][ri])
        tally.exact(np.asarray(got["t_start"], np.float64)[gi],
                    ref["t_start"][ri])
        tally.exact(np.asarray(got["t_end"], np.float64)[gi],
                    ref["t_end"][ri])
