"""``correct`` as the harness decides it, on the CPU at a tiny size: a
sound run of every cell passes, the lower-precision control fails, and so
does each fault planted in the timed path (an answer altered where it is
produced; half of the records left out).  The harness's look for a chip is
skipped; everything else is the run the benchmark makes."""

import os
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.tests import tiny

CELLS = tiny.CELLS
SEED = 2**32 + 3


def _run(workload, perturb, tmp_path):
    cell = tiny.cell(workload)
    return harness.run(cell, SEED, 1.0, False, time.perf_counter(),
                       harness.BENCH.parent, require_chip=False,
                       perturb=perturb, data_dir=str(tmp_path / "trace"),
                       log=lambda *a, **k: None)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload, tmp_path):
    out = _run(workload, None, tmp_path)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["compared"] > 0
    assert list(out)[-1] == "checks"
    assert "setup_s" in out["metrics"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload, tmp_path):
    out = _run(workload, "control-bf16", tmp_path)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["fault-alter", "fault-half"])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(workload, fault, tmp_path):
    out = _run(workload, fault, tmp_path)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reads_per_layer_metrics(workload, tmp_path):
    """On the CPU the host-side readers find their spans and counters; the
    device ones find no TPU plane and stay out of the line."""
    cell = tiny.cell(workload)
    out = harness.run(cell, SEED, 1.0, True, time.perf_counter(),
                      harness.BENCH.parent, require_chip=False,
                      data_dir=str(tmp_path / "trace"),
                      log=lambda *a, **k: None)
    assert out["correct"], out["checks"]
    host = {"canonical_sort_pct.scan"}
    want = {m["name"] for m in cell.per_layer} & host
    assert set(out["metrics"]) == want
    assert "busy_s" not in out["device"]


def test_no_tpu_no_result():
    r = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "not 'tpu'" in r.stderr
