"""``time_profile``: each call spreads its metric uniformly over
``[enter, leave)``; ``num_bins`` equal bins over the trace's ``[first row,
last row]``.  Compared cell by cell over the profile's largest cell: a
call's share of a bin comes from timestamps, so a nearly empty cell carries
the rounding of its bin's edges, not of its own sum."""

import numpy as np

from .. import selection


def reference(s, num_bins=32, metric=selection.EXC):
    t0, t1 = float(s.t_first), float(s.t_last)
    if t1 <= t0:
        t1 = t0 + 1.0
    edges = np.linspace(t0, t1, num_bins + 1)
    st = s.start.astype(np.float64)
    en = s.end.astype(np.float64)
    w = selection.metric(s, metric).astype(np.float64)
    prof = np.zeros((num_bins, len(s.names)))
    dur = en - st
    rate = np.where(dur > 0, w / np.maximum(dur, 1e-30), 0.0)
    first = np.clip(np.searchsorted(edges, st, side="right") - 1, 0,
                    num_bins - 1)
    last = np.clip(np.searchsorted(edges, en, side="left") - 1, 0,
                   num_bins - 1)
    # most calls sit in one bin; walk the few that cross edges bin by bin
    span = last - first
    for k in range(int(span.max()) + 1 if span.size else 0):
        sel = span >= k
        b = first[sel] + k
        ov = (np.minimum(en[sel], edges[b + 1])
              - np.maximum(st[sel], edges[b])).clip(min=0.0)
        np.add.at(prof, (b, s.name[sel]), ov * rate[sel])
    zero = (dur <= 0) & (w > 0)
    if np.any(zero):
        np.add.at(prof, (first[zero], s.name[zero]), w[zero])
    out = {"bin_start": edges[:-1], "bin_end": edges[1:]}
    for f in np.nonzero(prof.sum(axis=0) > 0)[0]:
        out[str(s.names[f])] = prof[:, f]
    return out


def compare(tally, got, ref):
    tally.exact(np.asarray(got["bin_start"]), ref["bin_start"])
    tally.exact(np.asarray(got["bin_end"]), ref["bin_end"])
    gnames = {c for c in got.columns if c not in ("bin_start", "bin_end")}
    rnames = {c for c in ref if c not in ("bin_start", "bin_end")}
    tally.values["exact_mismatch"] += len(gnames ^ rnames)
    both = sorted(gnames & rnames)
    if both:
        tally.profile(np.stack([np.asarray(got[c]) for c in both]),
                      np.stack([ref[c] for c in both]))
