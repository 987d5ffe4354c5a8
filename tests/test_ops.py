"""§IV analysis operations on structured tracegen apps."""

import numpy as np
import pytest

from repro import tracegen as tg
from repro.core.constants import NAME, PROC


def test_loimos_load_imbalance_finds_hot_procs():
    t = tg.loimos(nprocs=64, iters=3, hot_procs=(21, 22, 23))
    li = t.load_imbalance(num_processes=3)
    row = {n: i for i, n in enumerate(li[NAME])}
    idx = row["ComputeInteractions()"]
    top = li["Top processes"][idx]
    assert set(int(p) for p in top) <= {21, 22, 23}
    assert li["time.exc.imbalance"][idx] > 1.5


def test_comm_matrix_symmetric_neighbors():
    t = tg.stencil3d(nprocs=27, iters=2)
    cm = t.comm_matrix()
    assert np.allclose(cm, cm.T)               # symmetric exchange
    assert cm.diagonal().sum() == 0
    # three message-size clusters (face/edge/corner analog)
    counts, edges = t.message_histogram(bins=10)
    assert (counts > 0).sum() >= 2


def test_comm_over_time_bursty():
    t = tg.gol(nprocs=4, iters=6)
    vals, edges = t.comm_over_time(num_bins=16)
    assert vals.sum() > 0
    assert len(vals) == 16


def test_idle_time_ranking():
    t = tg.loimos(nprocs=16, iters=3, hot_procs=(3,))
    idle = t.idle_time(k=16)
    procs = idle[PROC].tolist()
    # hot proc 3 idles the least → should be last in most-idle ranking
    assert int(procs[-1]) == 3


def test_kripke_critical_path_crosses_processes():
    t = tg.kripke_sweep(nprocs=8, iters=2)
    cp = t.critical_path_analysis()[0]
    assert len(set(cp[PROC].tolist())) >= 4    # wavefront spans ranks


def test_gol_lateness_positive_for_laggard():
    t = tg.gol(nprocs=4, iters=5, imbalance=0.5)
    lb = t.lateness_by_process()
    assert np.asarray(lb["max_lateness"]).max() > 0


def test_tortuga_pattern_detection_counts_iterations():
    t = tg.tortuga(nprocs=8, iters=6)
    pats = t.detect_pattern(start_event="time-loop")
    assert len(pats) == 6


def test_axonn_overlap_ordering():
    """v2 (overlapped) must show more overlap and less exposed comm than v0."""
    bd = {v: tg.axonn_training(nprocs=4, iters=4, version=v)
          .comm_comp_breakdown() for v in (0, 1, 2)}
    ov = {v: np.asarray(b["overlap"]).mean() for v, b in bd.items()}
    comm = {v: np.asarray(b["comm_only"]).mean() for v, b in bd.items()}
    assert ov[2] > ov[0]
    assert comm[1] < comm[0]
    assert comm[2] < comm[0]


def test_multirun_scaling_study():
    from repro.core.trace import Trace
    traces = [tg.tortuga(nprocs=n, iters=3) for n in (4, 8, 16)]
    df = Trace.multirun_analysis(traces, top_n=6)
    assert "computeRhs" in list(df.columns) or "computeRhs" in list(df[df.columns[0]])


def test_time_profile_backend_registry():
    """time_profile backends dispatch through the registered table: unknown
    names fail loudly listing the options, and user backends register the
    same way the built-ins do."""
    from repro.core import ops_summary, registry

    t = tg.gol(nprocs=2, iters=2)
    with pytest.raises(ValueError, match="numpy.*pallas|pallas.*numpy"):
        t.time_profile(num_bins=8, backend="nope")

    @registry.register_backend("time_profile", "double")
    def _double(starts, ends, rate, name_codes, edges, nf):
        return 2 * ops_summary._exact_profile(starts, ends, rate,
                                              name_codes, edges, nf)

    try:
        a = t.time_profile(num_bins=8)
        b = t.time_profile(num_bins=8, backend="double")
        cols = [c for c in a.columns if c not in ("bin_start", "bin_end")]
        for c in cols:
            np.testing.assert_allclose(np.asarray(b[c]),
                                       2 * np.asarray(a[c]))
    finally:
        del registry.op_backends("time_profile")["double"]
    assert "double" not in registry.op_backends("time_profile")


def test_time_profile_pallas_backend_parity_fast():
    """Interpret-mode parity of the registered Pallas kernel backend on a
    small trace — the fast-tier guard that keeps the kernel exercised
    (the full sweep lives in tests/test_kernels.py, slow tier)."""
    t = tg.gol(nprocs=2, iters=2, seed=3)
    a = t.time_profile(num_bins=8)
    b = t.time_profile(num_bins=8, backend="pallas")
    cols = [c for c in a.columns if c not in ("bin_start", "bin_end")]
    assert cols == [c for c in b.columns if c not in ("bin_start", "bin_end")]
    for c in cols:
        np.testing.assert_allclose(np.asarray(b[c]), np.asarray(a[c]),
                                   rtol=1e-5, atol=1e-3)
