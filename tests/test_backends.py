"""Per-op backend registry property suite (accelerator-resident ops PR).

Every (op, backend) pair in the registry is exercised over the inputs that
historically break block-padded kernels: zero-duration calls, calls
straddling the profile window's bin edges, empty selections, record
counts that are not a multiple of the kernel block size, and name counts
that are not a multiple of the block size.  Non-numpy backends must agree
with the exact numpy reference to f32 rounding, and must be
digest-identical across every execution path — eager, streaming over a
pack, and parallel run-units over sharded jsonl (the merge_from seam).
"""

import numpy as np
import pytest

from repro import tracegen as tg
import repro.core.accel as accel
from repro.core import registry
from repro.core.constants import EXC, INC, NAME
from repro.core.executor import execute_parallel
from repro.core.filters import Filter
from repro.core.streaming import StreamingTrace, StreamingUnsupported
from repro.core.trace import Trace
from repro.readers.jsonl import write_jsonl
from repro.readers.pack import write_pack
from repro.serving.protocol import result_digest
from repro.tracegen.builder import TraceBuilder

KERNEL_OPS = ("flat_profile", "time_profile", "load_imbalance",
              "comm_matrix", "message_histogram", "stragglers")

OP_KWARGS = {
    "flat_profile": {"metrics": (EXC, INC)},
    "time_profile": {"num_bins": 8},
    "load_imbalance": {},
    "comm_matrix": {},
    "message_histogram": {"bins": 8},
    "stragglers": {"threshold": 0.05},
}

#: the ops whose pallas path sorts its records (message_histogram's
#: counts are exact whatever the order)
SORTING_OPS = tuple(op for op in KERNEL_OPS if op != "message_histogram")

PAIRS = [(op, b) for op in KERNEL_OPS for b in registry.list_backends(op)]
ACCEL = [(op, b) for op, b in PAIRS if b != "numpy"]


def assert_equivalent(op, a, b, context=""):
    """Backend result vs numpy reference: f32 rounding on sums, exact
    counts/edges, exact everything non-float."""
    if op == "comm_matrix":
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-3, err_msg=context)
        return
    if op == "message_histogram":
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]),
                                      err_msg=f"{context}: counts")
        np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b[1]),
                                   err_msg=f"{context}: edges")
        return
    assert list(a.columns) == list(b.columns), context
    assert len(a) == len(b), context
    for c in a.columns:
        va, vb = np.asarray(a[c]), np.asarray(b[c])
        if va.dtype.kind == "f":
            np.testing.assert_allclose(va, vb, rtol=1e-5, atol=1e-3,
                                       err_msg=f"{context}: column {c}")
        elif va.dtype == object:
            for x, y in zip(va, vb):
                assert np.array_equal(np.asarray(x), np.asarray(y)), \
                    f"{context}: column {c}"
        else:
            np.testing.assert_array_equal(va, vb,
                                          err_msg=f"{context}: column {c}")


# ---------------------------------------------------------------------------
# registry surface
# ---------------------------------------------------------------------------

def test_every_kernel_op_has_both_backends():
    for op in KERNEL_OPS:
        names = registry.list_backends(op)
        assert "numpy" in names and "pallas" in names, op
        assert names == sorted(names)
        assert list(registry.get_op(op).backends) == names


def test_unknown_backend_fails_loudly_listing_options():
    for op in KERNEL_OPS:
        with pytest.raises(ValueError,
                           match="numpy.*pallas|pallas.*numpy"):
            registry.get_backend(op, "nope")


def test_register_backend_roundtrip():
    @registry.register_backend("comm_matrix", "zeros_test")
    def _zeros(trace, **kw):
        n = 1
        return np.zeros((n, n))

    try:
        assert "zeros_test" in registry.list_backends("comm_matrix")
        assert registry.get_backend("comm_matrix", "zeros_test") is _zeros
        t = tg.stencil3d(nprocs=8, iters=1)
        assert t.comm_matrix(backend="zeros_test").sum() == 0
    finally:
        del registry.op_backends("comm_matrix")["zeros_test"]
    assert "zeros_test" not in registry.list_backends("comm_matrix")


# ---------------------------------------------------------------------------
# edge-input properties, every (op, backend) pair
# ---------------------------------------------------------------------------

def _edge_trace():
    """Deterministic trace with every pathological shape at once: zero
    duration calls, 7 names (not a block multiple), sends, and a call
    count that is not a multiple of any kernel block size."""
    tb = TraceBuilder()
    for p in range(3):
        t = float(p) * 0.1
        for i in range(161):                       # 3*161 = 483 calls
            # proc-dependent durations so per-proc totals are never exactly
            # tied (ties make top-process ranking rounding-sensitive)
            dur = 0.0 if i % 7 == 0 else (0.5 + ((i + 3 * p) % 5) * 0.25
                                          + p * 0.01)
            t = tb.call(t, dur, f"f{i % 7}", p)
        t = tb.send(t, 1.0, p, (p + 1) % 3, 64.0 * (p + 1))
        tb.recv(t, 1.0, p, (p - 1) % 3, 64.0 * ((p - 1) % 3 + 1))
    return tb.trace()


@pytest.fixture(scope="module")
def edge_trace():
    return _edge_trace()


@pytest.mark.parametrize("op,backend", ACCEL)
def test_zero_duration_and_padded_tail(edge_trace, op, backend):
    """483 call records (not a multiple of 256), 69 of them zero-duration,
    7 function names: the padded tail blocks and sentinel rows must not
    leak into the result."""
    kw = OP_KWARGS[op]
    ref = edge_trace.query().run(op, cache=False, backend="numpy", **kw)
    res = edge_trace.query().run(op, cache=False, backend=backend, **kw)
    assert_equivalent(op, ref, res, context=f"{op}/{backend}")


@pytest.mark.parametrize("backend",
                         registry.list_backends("time_profile"))
def test_time_profile_straddling_bins_conserves_mass(backend):
    """A call spanning the whole window plus calls straddling interior bin
    edges: every backend must spread each call's metric over its exact
    span, so per-function bin sums equal the call durations."""
    tb = TraceBuilder()
    tb.call(0.0, 9.0, "whole", 0)                  # spans all bins
    t = tb.call(1.4, 2.2, "straddle", 1)           # crosses 3.0 edge
    tb.call(t + 0.1, 5.0, "straddle", 1)           # crosses 6.0 edge
    tb.call(8.999, 0.001, "tail", 2)               # ends exactly at t1
    tr = tb.trace()
    prof = tr.time_profile(num_bins=3, backend=backend)
    sums = {c: float(np.asarray(prof[c]).sum()) for c in prof.columns
            if c not in ("bin_start", "bin_end")}
    assert sums["whole"] == pytest.approx(9.0, rel=1e-5)
    assert sums["straddle"] == pytest.approx(7.2, rel=1e-5)
    assert sums["tail"] == pytest.approx(0.001, rel=1e-3)
    # no call straddles t0/t1 themselves: total mass is conserved
    assert sum(sums.values()) == pytest.approx(16.201, rel=1e-5)


@pytest.mark.parametrize("op,backend", PAIRS)
def test_empty_selection(edge_trace, op, backend):
    """A filter that matches nothing must produce an empty (not crashed,
    not NaN) result on every backend."""
    kw = OP_KWARGS[op]
    res = (edge_trace.query()
           .filter(Filter(NAME, "==", "no_such_function"))
           .run(op, cache=False, backend=backend, **kw))
    if op == "comm_matrix":
        assert np.asarray(res).sum() == 0
    elif op == "message_histogram":
        assert np.asarray(res[0]).sum() == 0
    else:
        assert len(res) == 0


@pytest.mark.parametrize("backend",
                         registry.list_backends("time_profile"))
def test_time_profile_single_instant_trace(backend):
    """Degenerate trace whose events share one timestamp: no NaNs, no
    crash (regression for the zero-bin-width guard in the pallas
    backend)."""
    tb = TraceBuilder()
    tb.enter(5.0, "f", 0)
    tb.leave(5.0, "f", 0)
    tr = tb.trace()
    prof = tr.time_profile(num_bins=4, backend=backend)
    for c in prof.columns:
        assert np.isfinite(np.asarray(prof[c], float)).all(), c


# ---------------------------------------------------------------------------
# path identity: eager / streaming / parallel run-units
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def path_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("backends")
    tr, _gt = tg.pathology_trace("straggler", nprocs=4, iters=24,
                                 magnitude=2.0, seed=11)
    pack = str(d / "t.pack")
    jsonl = str(d / "t.jsonl")
    write_pack(tr, pack)
    write_jsonl(tr, jsonl)
    return pack, jsonl


@pytest.mark.parametrize("op,backend", ACCEL)
def test_digest_identical_across_paths(path_files, op, backend):
    """The accelerator contract: identical record multiset + canonical
    order + one kernel invocation ⇒ bit-identical results on every path."""
    pack, jsonl = path_files
    kw = OP_KWARGS[op]
    eager = Trace.open(pack).query().run(op, cache=False, backend=backend,
                                         **kw)
    stream = (Trace.open(pack, streaming=True, chunk_rows=97)
              .query().run(op, cache=False, backend=backend, **kw))
    spec = registry.get_op(op)
    agg = spec.streaming(backend=backend, **kw)
    par = execute_parallel(
        StreamingTrace(jsonl, chunk_rows=61, processes=2), (), spec,
        (), dict(kw, backend=backend), agg, n_units=4, use_pool=False)
    d0 = result_digest(eager)
    assert result_digest(stream) == d0, f"{op}/{backend}: streaming"
    assert result_digest(par) == d0, f"{op}/{backend}: parallel"


@pytest.fixture(scope="module")
def wide_files(tmp_path_factory):
    """600 ranks, so the 600 x 600 comm matrix spans 2 x 2 output tiles:
    each rank sends to its next and to the rank 300 on (the corner tiles),
    the last rank to no one past the end."""
    d = tmp_path_factory.mktemp("wide")
    tb = TraceBuilder()
    n = 600
    for p in range(n):
        t = tb.call(float(p % 7), 3.0, "compute", p)
        for dst in (p + 1, p + 300):
            if dst < n:
                t = tb.send(t, 2.0, p, dst, 1000.0 + 37 * p + dst)
    tr = tb.trace()
    pack, jsonl = str(d / "w.pack"), str(d / "w.jsonl")
    write_pack(tr, pack)
    write_jsonl(tr, jsonl)
    return pack, jsonl


def test_tiled_comm_matrix_digest_identical_across_paths(wide_files):
    """The same contract when pair_sum's output spans several tiles: the
    bucketing by tile is a stable function of the canonical order."""
    pack, jsonl = wide_files
    kw = {"backend": "pallas"}
    eager = Trace.open(pack).query().run("comm_matrix", cache=False, **kw)
    stream = (Trace.open(pack, streaming=True, chunk_rows=301)
              .query().run("comm_matrix", cache=False, **kw))
    spec = registry.get_op("comm_matrix")
    par = execute_parallel(
        StreamingTrace(jsonl, chunk_rows=257, processes=2), (), spec,
        (), kw, spec.streaming(**kw), n_units=4, use_pool=False)
    want = Trace.open(jsonl).comm_matrix()
    assert np.asarray(eager).shape == (600, 600)
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(want))
    d0 = result_digest(eager)
    assert result_digest(stream) == d0, "streaming"
    assert result_digest(par) == d0, "parallel"


def test_streaming_time_profile_pallas_no_longer_raises(path_files):
    """Regression: streaming time_profile used to hard-raise for any
    non-numpy backend instead of consulting the backend table."""
    pack, _ = path_files
    st = Trace.open(pack, streaming=True, chunk_rows=97)
    eager = Trace.open(pack).time_profile(num_bins=16, backend="pallas")
    stream = st.time_profile(num_bins=16, backend="pallas")
    assert result_digest(eager) == result_digest(stream)


@pytest.mark.parametrize("path", ["eager", "streaming"])
@pytest.mark.parametrize("op", SORTING_OPS)
def test_one_canonical_sort_per_run(path_files, monkeypatch, op, path):
    """Every sorting op reaches ``accel.canonical_order`` as a module
    attribute, once per run, on every path: a wrapper set on the module
    (as the benchmark's sort probe does) sees exactly one call."""
    seen = []
    orig = accel.canonical_order

    def canonical_order(*args, **kwargs):
        seen.append(len(args[0]))
        return orig(*args, **kwargs)

    monkeypatch.setattr(accel, "canonical_order", canonical_order)
    pack, _ = path_files
    trace = (Trace.open(pack, streaming=True, chunk_rows=97)
             if path == "streaming" else Trace.open(pack))
    trace.query().run(op, cache=False, backend="pallas", **OP_KWARGS[op])
    assert len(seen) == 1, f"{op}/{path}: {len(seen)} sorts"
    assert seen[0] > 0


@pytest.mark.parametrize("op", KERNEL_OPS)
def test_streaming_refuses_trace_level_backend(path_files, op):
    """A streaming aggregate runs only the backends it can feed records:
    a registered trace-level backend is refused with StreamingUnsupported,
    naming the op.  time_profile's backends are record-level by contract,
    so its aggregate takes a registered one and matches the eager run."""
    name = "registered_test"
    if op == "time_profile":
        fn = registry.get_backend("time_profile", "numpy")
    else:
        def fn(trace, **kwargs):
            raise AssertionError("a trace-level backend ran on a stream")
    registry.register_backend(op, name)(fn)
    try:
        kw = dict(OP_KWARGS[op], backend=name)
        if op == "time_profile":
            pack, _ = path_files
            eager = Trace.open(pack).time_profile(**kw)
            stream = Trace.open(pack, streaming=True,
                                chunk_rows=97).time_profile(**kw)
            assert result_digest(stream) == result_digest(eager)
        else:
            with pytest.raises(StreamingUnsupported,
                               match=rf"^streaming {op} supports backends "
                                     rf"\('numpy', 'pallas'\); "
                                     rf"'{name}' is trace-level"):
                registry.get_op(op).streaming(**kw)
    finally:
        del registry.op_backends(op)[name]
