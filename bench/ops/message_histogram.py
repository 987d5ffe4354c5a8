"""``message_histogram``: counts of message sizes in ``bins`` equal bins
over their range, and the bin edges; both exact."""

import numpy as np


def reference(s, bins=10):
    if s.m_size.size == 0:
        return np.zeros(bins, np.int64), np.linspace(0, 1, bins + 1)
    return np.histogram(s.m_size.astype(np.float64), bins=bins)


def compare(tally, got, ref):
    tally.exact(np.asarray(got[0]), ref[0])
    tally.exact(np.asarray(got[1]), ref[1])
