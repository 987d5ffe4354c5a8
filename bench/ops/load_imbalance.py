"""``load_imbalance``: per name, the ``time.exc`` totals of the ranks: mean
over ranks, max, max over mean, and the ``num_processes`` largest ranks."""

import numpy as np

from ..compare import rows_by_name
from ..selection import EXC, name_rank_totals


def reference(s, metric=EXC, num_processes=5):
    tot = name_rank_totals(s, metric)
    active = np.nonzero(tot.sum(axis=1) > 0)[0]
    mean = tot.sum(axis=1) / max(s.nprocs, 1)
    mx = tot.max(axis=1)
    imb = np.where(mean > 0, mx / np.maximum(mean, 1e-30), 0.0)
    return {"Name": s.names[active], f"{metric}.imbalance": imb[active],
            f"{metric}.mean": mean[active], f"{metric}.max": mx[active],
            "totals": tot[active], "num_processes": num_processes}


def compare(tally, got, ref):
    g = rows_by_name(got, "Name")
    r = {str(n): i for i, n in enumerate(ref["Name"])}
    tally.names(g, r)
    both = [n for n in r if n in g]
    gi = np.asarray([g[n] for n in both], np.int64)
    ri = np.asarray([r[n] for n in both], np.int64)
    for col in ("time.exc.mean", "time.exc.max", "time.exc.imbalance"):
        tally.sums(np.asarray(got[col])[gi], ref[col][ri])
    # the ranks it ranks first: their reference totals must be the largest
    # ones (ranks whose totals tie to rounding may swap)
    k = int(ref["num_processes"])
    tops = got["Top processes"]
    for a, b in zip(gi, ri):
        tot = ref["totals"][b]
        top = np.asarray(list(tops[a]), np.int64)
        best = np.sort(tot)[::-1][:k]
        if top.size != best.size or np.any((top < 0) | (top >= tot.size)):
            tally.values["exact_mismatch"] += 1
            continue
        tally.sums(tot[top], best)
