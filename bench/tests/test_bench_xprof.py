"""The profiler-trace reduction: busy and idle time over the window, device
time per jit program, and idle gaps named by the host span open in them.

One test builds planes by hand, so every number is known; the other reads
``data/tiny.xplane.pb``, recorded on one TPU v5e by ``record_trace.py``."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import xprof

MS = 1_000_000


def _ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS)


def _planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench:window", 0, 100),
        _ev("bench:request:flat_profile", 0, 100),
        _ev("bench:canonical_order", 20, 50),
        _ev("PjitFunction(f)", 5, 1)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            _ev("jit_segment_sum_matrix(7)", 10, 5),
            _ev("jit_segment_sum_matrix(7)", 12, 5),   # overlaps the first
            _ev("jit_pair_sum_matrix(9)", 80, 10),
            _ev("jit_pair_sum_matrix(9)", 150, 10)]),  # after the window
        NS(name="XLA Ops", events=[_ev("fusion.1", 10, 7),
                                   _ev("custom-call.2", 80, 10)])])
    core = NS(name="/device:TPU:0 SparseCore 0", lines=[])
    return [host, dev, core]


def test_reduction_of_known_planes():
    p = xprof.Profile(_planes())
    assert p.window_s == pytest.approx(0.1)
    assert p.busy_s == pytest.approx(0.017)          # [10, 17] and [80, 90]
    assert p.idle_pct() == pytest.approx(83.0)
    assert p.program_s("jit_segment_sum_matrix") == pytest.approx(0.010)
    assert p.program_s("jit_pair_sum_matrix") == pytest.approx(0.010)
    assert p.device_ops()[0] == ["jit_pair_sum_matrix/custom-call.2",
                                pytest.approx(0.010)]
    gaps = p.idle_gaps()
    assert gaps[0] == ["canonical_order", pytest.approx(0.063)]
    assert ["request:flat_profile", pytest.approx(0.010)] in gaps


def test_no_device_plane_reads_nothing():
    p = xprof.Profile(_planes()[:1])
    assert p.busy_s is None and p.idle_pct() is None and p.idle_gaps() == []


TINY = Path(__file__).parent / "data" / "tiny.xplane.pb"


def test_recorded_chip_trace():
    p = xprof.load(str(TINY))
    # two kernels ran, each well under the 50 ms and 20 ms host sleeps
    assert p.program_s("jit_segment_sum_matrix") > 0
    assert p.program_s("jit_pair_sum_matrix") > 0
    assert 0 < p.busy_s < 0.05
    assert 50 < p.idle_pct() < 100
    labels = [g[0] for g in p.idle_gaps()]
    assert labels[0] == "canonical_order"
    assert "handle_get" in labels[:3]
