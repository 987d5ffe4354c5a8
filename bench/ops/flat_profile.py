"""``flat_profile``: per name, the calls counted and each metric summed;
rows ordered by the first metric, descending (names alphabetically first,
so ties keep that order)."""

import numpy as np

from ..compare import rows_by_name
from ..selection import EXC, metric


def reference(s, metrics=(EXC,)):
    nf = len(s.names)
    counts = np.bincount(s.name, minlength=nf)
    present = np.nonzero(counts)[0]
    sums = {m: np.bincount(s.name, weights=metric(s, m).astype(np.float64),
                           minlength=nf) for m in metrics}
    alpha = present[np.argsort(s.names[present], kind="stable")]
    order = alpha[np.argsort(-sums[metrics[0]][alpha], kind="stable")]
    out = {"Name": s.names[order], "count": counts[order]}
    for m in metrics:
        out[m] = sums[m][order]
    return out


def compare(tally, got, ref):
    g = rows_by_name(got, "Name")
    r = {str(n): i for i, n in enumerate(ref["Name"])}
    tally.names(g, r)
    both = [n for n in r if n in g]
    if not both:
        return
    gi = np.asarray([g[n] for n in both], np.int64)
    ri = np.asarray([r[n] for n in both], np.int64)
    tally.exact(np.asarray(got["count"])[gi], ref["count"][ri])
    for m in ref:
        if m not in ("Name", "count"):
            tally.sums(np.asarray(got[m])[gi], ref[m][ri])
