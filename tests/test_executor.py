"""Parallel plan executor + plan-result cache tests (tentpole of PR 4).

The contract: for every registered op whose streaming aggregator declares a
cross-worker merge, multi-core execution over partitioned work units is
byte-identical to serial streaming and to in-memory eager execution —
including enter/leave pairs split across unit seams — and degradations back
to the serial path always warn with the concrete reason.  The plan cache
returns identical objects on repeat calls and never serves stale results.
"""

import json
import os
import warnings

import numpy as np
import pytest

from repro import tracegen
from repro.core import executor as ex
from repro.core import plancache, registry
from repro.core.constants import EXC, INC, NAME, PROC
from repro.core.diff import TraceSet
from repro.core.filters import Filter, time_window_filter
from repro.core.streaming import (StreamAgg, StreamingTrace,
                                  StreamingUnsupported)
from repro.core.trace import Trace
from repro.readers.jsonl import iter_lines_range, write_jsonl


def assert_frames_equal(a, b, tol=False, context=""):
    assert a.columns == b.columns, f"{context}: {a.columns} vs {b.columns}"
    for c in a.columns:
        va, vb = a[c], b[c]
        if np.asarray(va).dtype.kind in "UO":
            assert list(map(str, va)) == list(map(str, vb)), \
                f"{context}: column {c}"
        elif tol:
            np.testing.assert_allclose(np.asarray(va, float),
                                       np.asarray(vb, float),
                                       rtol=1e-9, atol=1e-6,
                                       err_msg=f"{context}: column {c}")
        else:
            np.testing.assert_array_equal(np.asarray(va), np.asarray(vb),
                                          err_msg=f"{context}: column {c}")


def run_units(path_or_paths, op, *args, n_units=4, chunk_rows=61, steps=(),
              **kwargs):
    """Partitioned execution with in-process workers: exercises unit
    planning, the deferring stitcher, and the merge — without pool cost."""
    h = StreamingTrace(path_or_paths, chunk_rows=chunk_rows, processes=2)
    spec = registry.get_op(op)
    agg = spec.streaming(*args, **kwargs)
    return ex.execute_parallel(h, tuple(steps), spec, args, kwargs, agg,
                               n_units=n_units, use_pool=False)


@pytest.fixture(autouse=True)
def _fresh_cache():
    plancache.clear()
    yield
    plancache.clear()


@pytest.fixture(scope="module")
def trace_file(tmp_path_factory):
    d = tmp_path_factory.mktemp("par")
    t = tracegen.tortuga(nprocs=4, iters=4, seed=3)
    path = str(d / "tortuga.jsonl")
    write_jsonl(t, path)
    return path


@pytest.fixture(scope="module")
def mem(trace_file):
    return Trace.open(trace_file)


# ---------------------------------------------------------------------------
# parity: every parallel-safe op, byte-identical across unit seams
# ---------------------------------------------------------------------------

# op -> (args, kwargs, comparison) — the completeness test below fails when
# an op gains a parallel merge without gaining coverage here
FRAME_EQ, FRAME_TOL, ARRAY_EQ, HIST_EQ = "frame", "frame_tol", "array", "hist"
PARALLEL_OPS = {
    "flat_profile": ((), {"metrics": [EXC, INC]}, FRAME_EQ),
    "load_imbalance": ((), {}, FRAME_EQ),
    "idle_time": ((), {}, FRAME_EQ),
    "comm_matrix": ((), {}, ARRAY_EQ),
    "comm_by_process": ((), {}, FRAME_EQ),
    "message_histogram": ((), {"bins": 7}, HIST_EQ),
    "comm_over_time": ((), {"num_bins": 16}, HIST_EQ),
    "time_profile": ((), {"num_bins": 24}, FRAME_TOL),
    # diagnostics suite: Findings / efficiency frames, exact accumulation
    "late_sender": ((), {"threshold": 0.0}, FRAME_EQ),
    "stragglers": ((), {"threshold": 0.0}, FRAME_EQ),
    "serialization": ((), {"threshold": 0.0}, FRAME_EQ),
    "imbalance_root_cause": ((), {"threshold": 0.0}, FRAME_EQ),
    "pop_efficiency": ((), {"threshold": 0.0}, FRAME_EQ),
    "efficiency_metrics": ((), {"num_windows": 12}, FRAME_EQ),
    "diagnose": ((), {}, FRAME_EQ),
}


def test_every_parallel_safe_op_is_covered():
    safe = {name for name in registry.list_ops()
            if registry.get_op(name).parallel_safe}
    assert safe == set(PARALLEL_OPS), \
        "new parallel-safe op registered without parity coverage"


@pytest.mark.parametrize("op", sorted(PARALLEL_OPS))
def test_parallel_identical_to_eager(trace_file, mem, op):
    args, kwargs, cmp = PARALLEL_OPS[op]
    a = getattr(mem, op)(*args, **kwargs)
    b = run_units(trace_file, op, *args, **kwargs)
    if cmp == FRAME_EQ:
        assert_frames_equal(a, b, context=op)
    elif cmp == FRAME_TOL:
        assert_frames_equal(a, b, tol=True, context=op)
    elif cmp == ARRAY_EQ:
        np.testing.assert_array_equal(a, b, err_msg=op)
    else:
        np.testing.assert_array_equal(a[0], b[0], err_msg=op)
        np.testing.assert_allclose(a[1], b[1], err_msg=op)


@pytest.mark.parametrize("n_units", [2, 7, 19])
def test_seam_stitching_at_any_unit_count(trace_file, mem, n_units):
    """main()/wrapper pairs span every unit seam; inc/exc must still match
    the in-memory structure pass exactly."""
    a = mem.flat_profile(metrics=[EXC, INC], per_process=True)
    b = run_units(trace_file, "flat_profile", n_units=n_units, chunk_rows=37,
                  metrics=[EXC, INC], per_process=True)
    assert_frames_equal(a, b, context=f"n_units={n_units}")


def test_parallel_with_plan_steps(trace_file, mem):
    f = (Filter(NAME, "not-in", ["MPI_Wait", "MPI_Isend"])
         & time_window_filter(0, 10**15, trim="within"))
    a = mem.query().filter(f).restrict_processes([0, 1, 3]).flat_profile()
    h = StreamingTrace(trace_file, chunk_rows=53, processes=2)
    q = h.query().filter(f).restrict_processes([0, 1, 3])
    spec = registry.get_op("flat_profile")
    b = ex.execute_parallel(h, q._steps, spec, (), {}, spec.streaming(),
                            n_units=5, use_pool=False)
    assert_frames_equal(a, b)


def test_parallel_identical_to_serial_streaming(trace_file):
    st = Trace.open(trace_file, streaming=True, chunk_rows=61, cache=False)
    serial = st.flat_profile(metrics=[EXC, INC])
    par = run_units(trace_file, "flat_profile", metrics=[EXC, INC])
    assert_frames_equal(serial, par, context="serial vs parallel streaming")


def test_sharded_paths_parallel(tmp_path):
    paths = tracegen.big_trace(str(tmp_path / "big"), nprocs=3,
                               events_per_proc=2500, calls_per_iter=100)
    mem = Trace.open(paths)
    assert_frames_equal(mem.flat_profile(),
                        run_units(paths, "flat_profile", chunk_rows=400))
    np.testing.assert_array_equal(mem.comm_matrix(),
                                  run_units(paths, "comm_matrix",
                                            chunk_rows=400))


def test_chrome_procspan_units(tmp_path):
    """Chrome traces partition per-pid (ProcSpan units with a shared pid
    table); non-dense pids must densify identically to the eager read."""
    p = str(tmp_path / "weird.json")
    events = []
    for pid in (5000, 300, 71):
        events += [{"ph": "B", "name": "work", "pid": pid, "tid": 0,
                    "ts": 1.0},
                   {"ph": "B", "name": "inner", "pid": pid, "tid": 0,
                    "ts": 10.0},
                   {"ph": "E", "name": "inner", "pid": pid, "tid": 0,
                    "ts": 20.0},
                   {"ph": "E", "name": "work", "pid": pid, "tid": 0,
                    "ts": 50.0}]
    with open(p, "w") as f:
        json.dump({"traceEvents": events}, f)
    mem = Trace.open(p)
    units = registry.get_reader("chrome").plan_units(p, 3)
    assert len(units) == 3
    assert all(isinstance(u, registry.ProcSpan) for u in units)
    assert_frames_equal(mem.flat_profile(per_process=True),
                        run_units(p, "flat_profile", n_units=3,
                                  chunk_rows=4, per_process=True))


def test_csv_units_guard_extra_columns(tmp_path):
    """Canonical-only CSVs byte-split; extra (value-inferred) columns make
    the file a single unit so per-span type decisions can never diverge
    from serial streaming."""
    canon = str(tmp_path / "canon.csv")
    with open(canon, "w") as f:
        f.write("Timestamp (ns),Event Type,Name,Process\n")
        for i in range(50):
            f.write(f"{i * 10},Enter,f,0\n{i * 10 + 5},Leave,f,0\n")
    units = registry.get_reader("csv").plan_units(canon, 3)
    assert units and all(isinstance(u, registry.ByteSpan) for u in units)
    mem = Trace.open(canon)
    assert_frames_equal(mem.flat_profile(),
                        run_units(canon, "flat_profile", n_units=3,
                                  chunk_rows=7))
    extra = str(tmp_path / "extra.csv")
    with open(extra, "w") as f:
        f.write("Timestamp (ns),Event Type,Name,Process,phase\n")
        f.write("0,Enter,f,0,1\n5,Leave,f,0,warmup\n")
    assert registry.get_reader("csv").plan_units(extra, 3) is None


def test_unit_plan_replans_when_file_grows(tmp_path):
    """Byte spans computed against an old file extent must not silently
    truncate a file that grew between terminal ops on one handle."""
    p = str(tmp_path / "grow.jsonl")
    t = tracegen.gol(nprocs=2, iters=2, seed=11)
    write_jsonl(t, p)
    h = StreamingTrace(p, chunk_rows=32, processes=2)
    spec = registry.get_op("flat_profile")
    r1 = ex.execute_parallel(h, (), spec, (), {}, spec.streaming(),
                             n_units=3, use_pool=False)
    with open(p, "a") as f:
        for i in range(50):
            f.write('{"ts": %d, "et": "Enter", "name": "grown", "proc": 0}\n'
                    '{"ts": %d, "et": "Leave", "name": "grown", "proc": 0}\n'
                    % (10**9 + i * 100, 10**9 + i * 100 + 50))
    r2 = ex.execute_parallel(h, (), spec, (), {}, spec.streaming(),
                             n_units=3, use_pool=False)
    assert "grown" in set(map(str, r2[NAME]))
    assert int(np.asarray(r2["count"]).sum()) \
        == int(np.asarray(r1["count"]).sum()) + 50


def test_csv_numeric_looking_names_in_one_span(tmp_path):
    """A byte span whose Name values all look numeric must still type the
    column categorically (pinned by name), not crash or diverge."""
    p = str(tmp_path / "numnames.csv")
    with open(p, "w") as f:
        f.write("Timestamp (ns),Event Type,Name,Process\n")
        for i in range(30):
            f.write(f"{i * 10},Enter,alpha,0\n{i * 10 + 5},Leave,alpha,0\n")
        for i in range(30, 60):
            f.write(f"{i * 10},Enter,123,0\n{i * 10 + 5},Leave,123,0\n")
    prof = run_units(p, "flat_profile", n_units=4, chunk_rows=8)
    assert set(map(str, prof[NAME])) == {"alpha", "123"}
    counts = dict(zip(map(str, prof[NAME]), np.asarray(prof["count"])))
    assert counts == {"alpha": 30, "123": 30}


def test_procspan_units_pruned_by_plan_restriction(tmp_path):
    """ProcSpan units disjoint from restrict_processes are never
    dispatched — workers must not decode a stream just to drop it all."""
    p = str(tmp_path / "pids.json")
    events = []
    for pid in range(4):
        events += [{"ph": "B", "name": "w", "pid": pid, "tid": 0, "ts": 1.0},
                   {"ph": "E", "name": "w", "pid": pid, "tid": 0, "ts": 9.0}]
    with open(p, "w") as f:
        json.dump({"traceEvents": events}, f)
    h = StreamingTrace(p, chunk_rows=4, processes=2)
    steps = h.query().restrict_processes([0, 1])._steps
    from repro.core.streaming import _steps_hints
    units = ex._prune_units(ex.plan_units(h, steps, 4), _steps_hints(steps))
    assert units and all(set(u.procs) & {0, 1} for u in units)
    assert len(units) < len(ex.plan_units(h, steps, 4))


def test_unit_plan_replans_on_dir_rewrite(tmp_path):
    """otf2j archives are directories: rewriting a contained file in place
    (dir mtime unchanged) must still re-plan units."""
    from repro.readers.otf2j import write_otf2_json
    d = str(tmp_path / "arch")
    write_otf2_json(tracegen.gol(nprocs=2, iters=2, seed=3), d,
                    split_locations=True)
    h = StreamingTrace(d, chunk_rows=50, processes=2)
    spec = registry.get_op("flat_profile")
    ex.execute_parallel(h, (), spec, (), {}, spec.streaming(), n_units=2,
                        use_pool=False)
    keys_before = set(h._units_cache)
    write_otf2_json(tracegen.gol(nprocs=4, iters=2, seed=3), d,
                    split_locations=True)
    prof = ex.execute_parallel(h, (), spec, (), {}, spec.streaming(),
                               n_units=2, use_pool=False)
    assert set(h._units_cache) != keys_before  # stat of inner files changed
    mem = Trace.open(d)
    assert_frames_equal(mem.flat_profile(), prof)


def test_open_rejects_cache_flag_without_streaming(trace_file):
    with pytest.raises(ValueError, match="cache"):
        Trace.open(trace_file, cache=False)


def test_unit_plans_cached_on_handle(trace_file):
    h = StreamingTrace(trace_file, chunk_rows=64, processes=2)
    spec = registry.get_op("flat_profile")
    ex.execute_parallel(h, (), spec, (), {}, spec.streaming(), n_units=3,
                        use_pool=False)
    assert h._units_cache
    (key, units), = h._units_cache.items()
    ex.execute_parallel(h, (), spec, (), {}, spec.streaming(), n_units=3,
                        use_pool=False)
    assert h._units_cache[key] is units  # re-planned from cache, not anew


def test_otf2j_rank_units(tmp_path):
    from repro.readers.otf2j import write_otf2_json
    t = tracegen.gol(nprocs=4, iters=3, seed=7)
    d = str(tmp_path / "arch")
    write_otf2_json(t, d, split_locations=True)
    mem = Trace.open(d)
    units = registry.get_reader("otf2j").plan_units(d, 2)
    assert units and all(isinstance(u, registry.ProcSpan) for u in units)
    assert_frames_equal(mem.flat_profile(per_process=True),
                        run_units(d, "flat_profile", n_units=2,
                                  chunk_rows=50, per_process=True))


_SPAWN_SCRIPT = """
import pickle, sys, warnings
sys.path.insert(0, {src!r})
from repro.core.trace import Trace

if __name__ == "__main__":  # spawned workers re-import this file
    st = Trace.open({path!r}, streaming=True, chunk_rows=101,
                    executor="parallel", processes=2, cache=False)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)  # no degradation
        prof = st.flat_profile(metrics=["time.exc", "time.inc"])
    # the handle keeps its pool: a second op must not restart workers
    pool = st._pool
    assert pool is not None
    imb = st.load_imbalance()
    assert st._pool is pool
    with open({out!r}, "wb") as f:
        pickle.dump((prof, imb), f)
"""


def test_spawn_pool_end_to_end(trace_file, mem, tmp_path):
    """The public API with a real spawn pool.  Spawned workers re-import
    ``__main__``, so the pool is started from a script file of its own: under
    pytest-xdist the worker's ``__main__`` has no file and the pool would
    (rightly) degrade to serial."""
    import pickle
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = str(tmp_path / "results.pkl")
    script = tmp_path / "spawn_e2e.py"
    script.write_text(_SPAWN_SCRIPT.format(src=os.path.abspath(src),
                                           path=trace_file, out=out))
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    with open(out, "rb") as f:
        prof, imb = pickle.load(f)
    assert_frames_equal(mem.flat_profile(metrics=[EXC, INC]), prof)
    assert_frames_equal(mem.load_imbalance(), imb)


def test_traceset_members_share_one_pool(tmp_path):
    before, after = tracegen.regression_pair(
        "tortuga", func="computeRhs", factor=1.7, nprocs=4, iters=3)
    pb, pa = str(tmp_path / "b.jsonl"), str(tmp_path / "a.jsonl")
    write_jsonl(before, pb)
    write_jsonl(after, pa)
    ts_mem = TraceSet.open([pb, pa])
    ts_par = TraceSet.open([pb, pa], streaming=True, chunk_rows=128,
                           processes=2)
    assert ts_par[0]._pool is not None
    assert len({id(m._pool) for m in ts_par}) == 1
    assert_frames_equal(ts_mem.regression_report(),
                        ts_par.regression_report())
    a, b = ts_mem.scaling_analysis(), ts_par.scaling_analysis()
    np.testing.assert_allclose(np.asarray(a["time.exc.total"], float),
                               np.asarray(b["time.exc.total"], float))


# ---------------------------------------------------------------------------
# degradation paths report why
# ---------------------------------------------------------------------------

def _degradation_warning(handle, op="flat_profile"):
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        getattr(handle, op)(cache=False)
    msgs = [str(x.message) for x in w
            if issubclass(x.category, RuntimeWarning)]
    assert msgs, "expected a degradation warning"
    return "\n".join(msgs)


def test_degradation_reason_processes_1(trace_file):
    st = Trace.open(trace_file, streaming=True, chunk_rows=64,
                    executor="parallel", processes=1)
    assert "processes=1" in _degradation_warning(st)


def test_degradation_reason_spawn_unsafe(trace_file, monkeypatch):
    monkeypatch.setattr(ex, "spawn_unsafe_reason",
                        lambda: "__main__ has no importable file (test)")
    st = Trace.open(trace_file, streaming=True, chunk_rows=64,
                    executor="parallel", processes=2)
    assert "__main__" in _degradation_warning(st)


def test_degradation_reason_non_mergeable_op(trace_file):
    @registry.register_op("last_ts_op")
    def last_ts_op(trace):
        return float(np.asarray(trace.events["Timestamp (ns)"]).max())

    @registry.register_streaming("last_ts_op")
    class _LastTsAgg(StreamAgg):
        # combinable but (deliberately) not parallel-mergeable
        def __init__(self):
            self.v = -np.inf

        def update(self, chunk):
            self.v = max(self.v, float(
                np.asarray(chunk.events["Timestamp (ns)"]).max()))

        def result(self, ctx):
            return self.v

    assert not registry.get_op("last_ts_op").parallel_safe
    st = Trace.open(trace_file, streaming=True, chunk_rows=64,
                    executor="parallel", processes=2)
    msg = _degradation_warning(st, "last_ts_op")
    assert "last_ts_op" in msg and "no cross-worker merge" in msg


def test_degradation_reason_unsplittable_input(tmp_path):
    """A single chrome file with one pid has no second work unit."""
    p = str(tmp_path / "one.json")
    with open(p, "w") as f:
        json.dump({"traceEvents": [
            {"ph": "B", "name": "f", "pid": 0, "tid": 0, "ts": 1.0},
            {"ph": "E", "name": "f", "pid": 0, "tid": 0, "ts": 9.0}]}, f)
    st = Trace.open(p, streaming=True, chunk_rows=64,
                    executor="parallel", processes=2)
    assert "cannot be partitioned" in _degradation_warning(st)


def test_cross_unit_out_of_order_raises(tmp_path):
    """A (proc, thread) stream that runs backwards between file halves must
    fail loudly under partitioned execution, like serial streaming does."""
    p = str(tmp_path / "backwards.jsonl")
    with open(p, "w") as f:
        for ts in (1000, 2000, 3000, 4000):
            f.write('{"ts": %d, "et": "Enter", "name": "a", "proc": 0}\n'
                    % ts)
        for ts in (10, 20, 30, 40):
            f.write('{"ts": %d, "et": "Leave", "name": "a", "proc": 0}\n'
                    % ts)
    with pytest.raises(StreamingUnsupported, match="time order"):
        run_units(p, "flat_profile", n_units=2, chunk_rows=2)


# ---------------------------------------------------------------------------
# byte-span line ownership
# ---------------------------------------------------------------------------

def test_byte_spans_partition_lines_exactly(tmp_path):
    p = str(tmp_path / "lines.txt")
    lines = [("line-%03d" % i).encode() + b"\n" for i in range(37)]
    with open(p, "wb") as f:
        f.writelines(lines)
    size = os.path.getsize(p)
    for n in (1, 2, 3, 5, 11, size):
        edges = [size * i // n for i in range(n + 1)]
        got = []
        for lo, hi in zip(edges[:-1], edges[1:]):
            with open(p, "rb") as f:
                got.extend(iter_lines_range(f, lo, hi))
        assert got == lines, f"n={n}"


# ---------------------------------------------------------------------------
# plan-result cache
# ---------------------------------------------------------------------------

def test_cache_hit_returns_identical_object(trace_file):
    st = Trace.open(trace_file, streaming=True, chunk_rows=256)
    r1 = st.flat_profile()
    r2 = st.flat_profile()
    assert r2 is r1
    assert plancache.stats()["hits"] >= 1


def test_cache_false_bypasses(trace_file):
    st = Trace.open(trace_file, streaming=True, chunk_rows=256)
    r1 = st.flat_profile()
    assert st.flat_profile(cache=False) is not r1
    st2 = Trace.open(trace_file, streaming=True, chunk_rows=256, cache=False)
    assert st2.flat_profile() is not r1


def test_cache_digest_differs_across_args_and_steps(trace_file):
    st = Trace.open(trace_file, streaming=True, chunk_rows=256)
    r1 = st.flat_profile()
    r2 = st.flat_profile(metrics=[INC])
    assert r2 is not r1
    r3 = st.query().restrict_processes([0, 1]).flat_profile()
    assert r3 is not r1
    # identical plan through a fresh handle over the same file still hits
    st2 = Trace.open(trace_file, streaming=True, chunk_rows=256)
    assert st2.flat_profile() is r1


def test_cache_invalidated_by_file_mutation(tmp_path):
    t = tracegen.gol(nprocs=2, iters=2, seed=9)
    p = str(tmp_path / "g.jsonl")
    write_jsonl(t, p)
    st = Trace.open(p, streaming=True, chunk_rows=64)
    r1 = st.flat_profile()
    with open(p, "a") as f:
        f.write('{"ts": 99999999999, "et": "Enter", "name": "zz", '
                '"proc": 0}\n')
    r2 = st.flat_profile()
    assert r2 is not r1  # size/mtime changed -> new digest


def test_cache_eager_opt_in_and_mutation(trace_file, mem):
    r1 = mem.query().flat_profile(cache=True)
    assert mem.query().flat_profile(cache=True) is r1
    # default for in-memory traces is uncached (content hash is O(N))
    assert mem.query().flat_profile() is not r1
    # mutating the events changes the content hash -> miss
    t = Trace.open(trace_file)
    a = t.query().flat_profile(cache=True)
    ev = t.events
    ts = np.asarray(ev["Timestamp (ns)"], np.int64).copy()
    ts[0] += 1
    ev["Timestamp (ns)"] = ts
    b = t.query().flat_profile(cache=True)
    assert b is not a


def test_cache_clear(trace_file):
    st = Trace.open(trace_file, streaming=True, chunk_rows=256)
    r1 = st.flat_profile()
    plancache.clear()
    assert st.flat_profile() is not r1


def test_cache_skips_undigestable_arguments(mem):
    # a callable argument has no exact digest -> bypass, never a wrong hit
    r1 = mem.query().comm_comp_breakdown(
        cache=True, comm_matcher=lambda n: n.startswith("MPI"))
    r2 = mem.query().comm_comp_breakdown(
        cache=True, comm_matcher=lambda n: False)
    assert r1 is not r2
    assert plancache.stats()["entries"] == 0
