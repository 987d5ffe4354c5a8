"""``comm_matrix``: bytes (``output="size"``) or messages sent from each rank
to each rank."""

import numpy as np


def reference(s, output="size"):
    n = s.nprocs
    w = s.m_size.astype(np.float64) if output == "size" else \
        np.ones(s.m_size.size)
    return np.bincount(s.m_proc * n + s.m_partner, weights=w,
                       minlength=n * n).reshape(n, n)


def compare(tally, got, ref):
    tally.sums(got, ref)
