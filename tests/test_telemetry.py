"""The runtime tracer's spans and counters: self time, request ids and
parents across threads and coroutines, the recorder into a Pipit trace,
the pack reader's io view, and the served scan path's ``/stats``
telemetry."""

import asyncio
import contextvars
import os
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro.core import NAME, plancache
from repro.core.trace import Trace
from repro.readers import pack
from repro.runtime import tracer
from repro.runtime.tracer import Tracer
from repro.serving.client import RemoteError, ServiceClient
from repro.serving.tracequery import TraceServer, TraceService
from repro.tracegen.big import big_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the spans and counters of the served scan path, by layer
SERVED_SPANS = (
    "client.request", "service.request", "service.admit", "service.queue",
    "service.handle", "service.execute", "service.encode",
    "read.open", "read.chunk",
    "stream.stats", "stream.intern", "stream.stitch", "stream.update",
    "stream.finalize",
    "accel.sort", "accel.kernel")
SERVED_COUNTERS = ("read.chunks_read", "read.chunks_skipped", "read.rows",
                   "accel.h2d_bytes")


def _delta(before, after, section, name, field=None):
    a = before[section].get(name, {} if field else 0)
    b = after[section].get(name, {} if field else 0)
    if field is None:
        return b - a
    return b.get(field, 0) - a.get(field, 0)


@pytest.fixture(scope="module")
def pack_paths(tmp_path_factory):
    out = tmp_path_factory.mktemp("tel_trc")
    big_trace(str(out), nprocs=4, events_per_proc=600, calls_per_iter=40,
              seed=5, format="pack")
    return sorted(str(p) for p in out.glob("*.pack"))


@pytest.fixture(scope="module")
def chunked_pack(pack_paths, tmp_path_factory):
    """One pack of the same events in many footer chunks, so a process
    restriction skips some of them."""
    path = str(tmp_path_factory.mktemp("tel_chunked") / "all.pack")
    pack.write_pack(Trace.open(pack_paths), path, chunk_rows=300)
    return path


# ---------------------------------------------------------------------------
# the facility
# ---------------------------------------------------------------------------

def test_self_time_excludes_same_thread_children():
    s0 = tracer.snapshot()
    with tracer.span("t.outer") as outer:
        with tracer.span("t.inner") as inner:
            time.sleep(0.02)
        time.sleep(0.01)
    s1 = tracer.snapshot()
    assert _delta(s0, s1, "spans", "t.outer", "count") == 1
    assert _delta(s0, s1, "spans", "t.outer", "total_ns") == \
        outer.duration_ns
    assert _delta(s0, s1, "spans", "t.outer", "self_ns") == \
        outer.duration_ns - inner.duration_ns
    assert _delta(s0, s1, "spans", "t.inner", "self_ns") == \
        inner.duration_ns
    assert inner.parent is outer and inner.request == outer.request


def test_exception_still_closes_the_span():
    s0 = tracer.snapshot()
    with pytest.raises(KeyError):
        with tracer.span("t.raises") as sp:
            raise KeyError("boom")
    s1 = tracer.snapshot()
    assert sp.duration_ns is not None
    assert _delta(s0, s1, "spans", "t.raises", "count") == 1
    with tracer.span("t.after") as after:
        pass
    assert after.parent is None   # the failed span is no longer current


def test_lane_thread_span_carries_request_and_parent():
    seen = {}

    def lane_work():
        with tracer.span("t.lane") as sp:
            seen["sp"] = sp
            seen["thread"] = threading.get_ident()

    with ThreadPoolExecutor(1) as pool:
        with tracer.span("t.request") as req:
            queued = tracer.begin("t.queue")

            def run():
                queued.end()
                lane_work()
            pool.submit(contextvars.copy_context().run, run).result()
    sp = seen["sp"]
    assert seen["thread"] != threading.get_ident()
    assert sp.parent is req and sp.request == req.request
    assert queued.parent is req and queued.duration_ns >= 0
    # a child on another thread is not taken from the parent's self time
    assert req.child_ns == queued.duration_ns


def test_interleaved_coroutines_keep_their_own_parents():
    seen = {}

    async def request(tag, first, second):
        with tracer.span("t.coro." + tag) as top:
            await first.wait()
            with tracer.span("t.coro.child") as child:
                second.set()
                await asyncio.sleep(0.001)
            seen[tag] = (top, child)

    async def main():
        a_go, b_go = asyncio.Event(), asyncio.Event()
        # a opens first, b runs its child first, then a: the spans of the
        # two requests interleave on the one event-loop thread
        b_go.set()
        await asyncio.gather(request("a", a_go, asyncio.Event()),
                             request("b", b_go, a_go))

    asyncio.run(main())
    for tag in ("a", "b"):
        top, child = seen[tag]
        assert child.parent is top and child.request == top.request
    assert seen["a"][0].request != seen["b"][0].request


def test_core_import_leaves_jax_out():
    code = ("import sys, repro.core, repro.readers, repro.runtime.tracer; "
            "from repro.runtime import tracer; "
            "from repro.serving import tracequery, client; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_counters_add_up():
    s0 = tracer.snapshot()
    tracer.counter("t.count")
    tracer.counter("t.count", 41)
    assert _delta(s0, tracer.snapshot(), "counters", "t.count") == 42


def test_totals_lose_no_update_across_threads():
    """More threads than cores, switching as often as the interpreter
    allows: every span, counter and recorded event is counted once."""
    n_threads, per = min(os.cpu_count() or 1, 64) + 3, 300
    rec = Tracer()
    s0 = tracer.snapshot()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per):
                with tracer.span("t.stress"):
                    tracer.counter("t.stress")
        with tracer.record_spans(rec):
            threads = [threading.Thread(target=work)
                       for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    s1 = tracer.snapshot()
    total = n_threads * per
    assert _delta(s0, s1, "spans", "t.stress", "count") == total
    assert _delta(s0, s1, "counters", "t.stress") == total
    assert len(rec.ts) == 2 * total
    prof = rec.to_trace().flat_profile(metrics=["time.exc"])
    assert prof["time.exc"][0] == _delta(s0, s1, "spans", "t.stress",
                                         "self_ns")


@pytest.mark.parametrize("kernel,args,per_record", [
    # code int32, two value rows f32
    ("seg_sum", lambda n: (np.arange(n) % 3, np.ones((n, 2)), 3), 12),
    # a, b int32, w f32
    ("pair_sum", lambda n: (np.arange(n) % 4, np.arange(n) % 5,
                            np.ones(n), 4, 5), 12),
    # coordinates f32
    ("hist_counts", lambda n: (np.arange(n) % 6, 6), 4),
])
def test_h2d_bytes_count_the_uploaded_operands(kernel, args, per_record):
    from repro.core import accel
    n = 1000
    s0 = tracer.snapshot()
    getattr(accel, kernel)(*args(n))
    s1 = tracer.snapshot()
    assert _delta(s0, s1, "counters", "accel.h2d_bytes") == per_record * n
    assert _delta(s0, s1, "spans", "accel.kernel", "count") == 1


# ---------------------------------------------------------------------------
# the recorder and the pack reader's io view
# ---------------------------------------------------------------------------

def test_record_spans_opens_in_pipit_with_the_same_self_time(pack_paths):
    st = Trace.open(pack_paths, streaming=True, chunk_rows=500)
    rec = Tracer()
    s0 = tracer.snapshot()
    with tracer.record_spans(rec):
        st.query().run("flat_profile", metrics=["time.exc"],
                       backend="pallas", cache=False)
    s1 = tracer.snapshot()
    prof = rec.to_trace().flat_profile(metrics=["time.exc"])
    names = [str(n) for n in np.asarray(prof[NAME])]
    assert {"read.chunk", "stream.stitch", "stream.finalize",
            "accel.sort", "accel.kernel"} <= set(names)
    for name, exc in zip(names, np.asarray(prof["time.exc"])):
        assert exc == _delta(s0, s1, "spans", name, "self_ns"), name


def test_io_stats_is_a_view_of_the_read_counters(chunked_pack):
    pack.reset_io_stats()
    assert set(pack.io_stats().values()) == {0}
    s0 = tracer.snapshot()
    st = Trace.open(chunked_pack, streaming=True)
    st.query().restrict_processes([0]).flat_profile()
    io = pack.io_stats()
    s1 = tracer.snapshot()
    assert io["chunks_read"] > 0 and io["chunks_skipped"] > 0
    for key, n in io.items():
        assert n == _delta(s0, s1, "counters", "read." + key)
    pack.reset_io_stats()
    assert set(pack.io_stats().values()) == {0}
    # the counters themselves never reset
    assert _delta(s0, tracer.snapshot(), "counters",
                  "read.chunks_read") == io["chunks_read"]


# ---------------------------------------------------------------------------
# the served scan path
# ---------------------------------------------------------------------------

def _serve(work):
    async def main():
        server = await TraceServer(TraceService(), port=0).start()
        try:
            return await asyncio.to_thread(work, server.port)
        finally:
            await server.shutdown(grace=5)
    return asyncio.run(main())


@pytest.fixture(scope="module")
def served_telemetry(pack_paths, chunked_pack):
    """``/stats["telemetry"]`` before and after a served mix of scans, one
    of them restricted to a process and one that fails."""
    plancache.clear()

    def work(port):
        with ServiceClient("127.0.0.1", port) as c:
            before = c.stats()["telemetry"]
            whole = c.open(pack_paths, streaming=True).query()
            whole.run("flat_profile", metrics=["time.exc", "time.inc"],
                      backend="pallas", cache=False)
            whole.run("time_profile", num_bins=8, backend="pallas",
                      cache=False)
            whole.run("comm_matrix", output="size", backend="pallas",
                      cache=False)
            (c.open(chunked_pack, streaming=True).query()
             .restrict_processes([0]).run("flat_profile", cache=False))
            with pytest.raises(RemoteError):
                c.open("/no/such/trace.pack").query().run("flat_profile")
            return before, c.stats()["telemetry"]

    return _serve(work)


def test_served_scan_fills_stats_telemetry(served_telemetry):
    before, after = served_telemetry
    for name in SERVED_SPANS:
        assert _delta(before, after, "spans", name, "count") > 0, name
    for name in SERVED_COUNTERS:
        assert _delta(before, after, "counters", name) > 0, name


@pytest.mark.parametrize("name", SERVED_SPANS)
def test_served_span_self_time_lies_within_its_duration(served_telemetry,
                                                        name):
    """Each layer's self time is what a share of the window is read from:
    it is positive and never more than the span's own duration."""
    before, after = served_telemetry
    total = _delta(before, after, "spans", name, "total_ns")
    self_ns = _delta(before, after, "spans", name, "self_ns")
    assert 0 < self_ns <= total, (self_ns, total)


def test_served_scan_spans_grow_with_chunks_not_records(pack_paths):
    """One pass records each span at most a small constant times per
    chunk, whatever the number of records."""
    plancache.clear()

    def work(port):
        with ServiceClient("127.0.0.1", port) as c:
            q = c.open(pack_paths, streaming=True, chunk_rows=400).query()
            q.run("flat_profile", backend="pallas", cache=False)
            before = c.stats()["telemetry"]
            q.run("flat_profile", backend="pallas", cache=False)
            return before, c.stats()["telemetry"]

    before, after = _serve(work)
    chunks = _delta(before, after, "spans", "read.chunk", "count")
    rows = _delta(before, after, "counters", "read.rows")
    assert 0 < chunks and 4 * chunks < rows
    for name in after["spans"]:
        assert _delta(before, after, "spans", name, "count") <= \
            2 * chunks + 2, name


def test_elapsed_ms_is_the_execute_span(pack_paths):
    plancache.clear()

    def work(port):
        with ServiceClient("127.0.0.1", port) as c:
            before = c.stats()["telemetry"]
            c.open(pack_paths, streaming=True).query().run(
                "flat_profile", cache=False)
            return before, c.last_meta, c.stats()["telemetry"]

    before, meta, after = _serve(work)
    ns = _delta(before, after, "spans", "service.execute", "total_ns")
    assert meta["elapsed_ms"] == round(ns / 1e6, 3)
