"""``accel.canonical_order`` is ``np.lexsort``'s stable five-key order
(start, end, process, name code, value, then the input index) on every
input, and ``accel.sort_tied`` counts the records its tie pass took."""

from collections import Counter

import numpy as np
import pytest

from repro.core import accel
from repro.runtime import tracer

SPECIALS = np.array([np.nan, -0.0, 0.0, np.inf, -np.inf, 1.0, 2.0])


def _records(start, end=None, proc=None, code=None, value=None):
    n = len(start)
    zero_f, zero_i = np.zeros(n), np.zeros(n, np.int64)
    return (np.asarray(start, np.float64),
            zero_f if end is None else np.asarray(end, np.float64),
            zero_i if proc is None else np.asarray(proc, np.int64),
            zero_i if code is None else np.asarray(code, np.int64),
            zero_f if value is None else np.asarray(value, np.float64))


def _random(rng, n=2000):
    start = rng.integers(0, 500, n).astype(float)
    return _records(start, start + rng.integers(0, 50, n),
                    rng.integers(0, 8, n), rng.integers(0, 5, n),
                    rng.integers(0, 3, n).astype(float))


def _stitched_runs(rng, ranks=16, per_rank=200):
    """One sorted run of calls per rank, as the stitcher emits each rank's
    chunk: starts rise strictly within a rank and tie only across ranks."""
    parts = []
    for r in range(ranks):
        start = np.cumsum(rng.integers(1, 40, per_rank)).astype(float)
        parts.append(_records(start, start + rng.integers(0, 30, per_rank),
                              np.full(per_rank, r), rng.integers(0, 18, per_rank),
                              rng.random(per_rank)))
    return tuple(np.concatenate(c) for c in zip(*parts))


def _reversed(rng):
    return tuple(c[::-1] for c in _stitched_runs(rng))


def _all_starts_equal(rng, n=500):
    return _records(np.full(n, 7.0), rng.integers(7, 20, n),
                    rng.integers(0, 6, n), rng.integers(0, 4, n),
                    rng.random(n))


def _proc_decides(rng, n=500):
    start = rng.integers(0, 5, n).astype(float)
    return _records(start, start, rng.permutation(n), rng.integers(0, 4, n),
                    rng.random(n))


def _value_decides(rng, n=500):
    return _records(np.zeros(n), np.ones(n), np.full(n, 3),
                    np.full(n, 2), rng.permutation(n).astype(float))


def _full_duplicates(rng, n=500):
    i = rng.integers(0, 4, n)
    return _records(i.astype(float), i + 1.0, i, i, i * 0.5)


def _specials(rng, n=300):
    return _records(rng.choice(SPECIALS, n), rng.choice(SPECIALS, n),
                    rng.integers(0, 2, n), rng.integers(0, 2, n),
                    rng.choice(SPECIALS, n))


def _empty(rng):
    return _records(np.zeros(0))


def _one(rng):
    return _records([3.0], [4.0], [1], [2], [0.5])


CASES = {
    "random": _random,
    "stitched_runs": _stitched_runs,
    "reversed": _reversed,
    "all_starts_equal": _all_starts_equal,
    "proc_decides": _proc_decides,
    "value_decides": _value_decides,
    "full_duplicates": _full_duplicates,
    "nan_zero_inf": _specials,
    "empty": _empty,
    "one": _one,
}


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("case", CASES)
def test_canonical_order_is_the_five_key_lexsort(case, seed):
    start, end, proc, code, value = CASES[case](np.random.default_rng(seed))
    got = accel.canonical_order(start, end, proc, code, value)
    want = np.lexsort((value, code, proc, end, start))
    np.testing.assert_array_equal(got, want)


def test_canonical_order_on_many_sets_of_special_values():
    """Small sets whose keys all come from NaN, ±0.0, ±inf and two finite
    values: ties at every level, NaN groups and signed zeros."""
    rng = np.random.default_rng(11)
    for _ in range(300):
        cols = _specials(rng, int(rng.integers(1, 40)))
        start, end, proc, code, value = cols
        np.testing.assert_array_equal(
            accel.canonical_order(*cols),
            np.lexsort((value, code, proc, end, start)))


def _tied(start):
    """The records whose start equals another record's: NaN equals NaN and
    -0.0 equals 0.0, as the sort's tie groups have them."""
    keys = ["nan" if np.isnan(s) else s + 0.0 for s in start]
    n = Counter(keys)
    return sum(1 for k in keys if n[k] > 1)


@pytest.mark.parametrize("case", ["distinct", "stitched_runs", "nan_zero_inf",
                                  "all_starts_equal", "empty"])
def test_sort_tied_counts_the_tied_records(case):
    rng = np.random.default_rng(5)
    if case == "distinct":
        cols = _records(rng.permutation(1000).astype(float))
    else:
        cols = CASES[case](rng)
    want = _tied(cols[0])
    if case == "distinct":
        assert want == 0
    before = tracer.count("accel.sort_tied")
    accel.canonical_order(*cols)
    assert tracer.count("accel.sort_tied") - before == want
