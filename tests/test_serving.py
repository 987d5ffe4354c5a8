"""Trace-query service tests: wire protocol fidelity, per-op conformance
against direct library calls, single-flight coalescing, admission control
(per-tenant concurrency + plan-cache quotas, lane starvation), graceful
shutdown, and the HTTP client round trip."""

import asyncio
import json
import time

import numpy as np
import pytest

from repro.core import plancache, registry
from repro.core.diff import TraceSet
from repro.core.filters import Filter
from repro.core.frame import Categorical, EventFrame
from repro.core.scheduler import Scheduler, set_scheduler
from repro.core.trace import Trace
from repro.serving import protocol
from repro.serving.client import RemoteError, ServiceClient
from repro.serving.protocol import ProtocolError, result_digest
from repro.serving.tracequery import (ServiceError, TraceServer,
                                      TraceService)
from repro.tracegen.big import big_trace


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pack_paths(tmp_path_factory):
    out = tmp_path_factory.mktemp("serve_trc")
    big_trace(str(out), nprocs=4, events_per_proc=600, calls_per_iter=40,
              seed=11, format="pack")
    return sorted(str(p) for p in out.glob("*.pack"))


@pytest.fixture()
def fresh_cache():
    plancache.clear()
    plancache.configure(enabled=True, tenant_quota=0)
    yield
    plancache.clear()
    plancache.configure(enabled=True, tenant_quota=0)


@pytest.fixture()
def sleep_op():
    @registry.register_op("_serve_sleep")
    def _serve_sleep(trace, duration=0.2, tag=0):
        time.sleep(float(duration))
        return float(len(trace.events)) + float(tag)

    yield "_serve_sleep"
    registry._OP_REGISTRY.pop("_serve_sleep", None)


def run(coro):
    return asyncio.run(coro)


def payload(paths, op, steps=None, streaming=False, tenant="t", args=(),
            kwargs=None, **extra):
    body = {"open": {"paths": list(paths), "streaming": streaming},
            "op": op, "steps": steps or [], "tenant": tenant,
            "args": [protocol.encode_value(a) for a in args],
            "kwargs": {k: protocol.encode_value(v)
                       for k, v in (kwargs or {}).items()}}
    body.update(extra)
    return body


def set_payload(paths, op, **extra):
    body = payload(paths, op, **extra)
    body["open"]["mode"] = "set"
    return body


async def one(service, body, set_scope=False):
    return await service.query(body, set_scope=set_scope)


# ---------------------------------------------------------------------------
# protocol unit tests
# ---------------------------------------------------------------------------

def test_value_roundtrip_bit_exact():
    ev = EventFrame({"Name": ["a", "b", "a"],
                     "x": np.asarray([1.5, np.nan, 3.0]),
                     "n": np.asarray([1, 2, 3], np.int64)})
    values = [ev, np.arange(12, dtype=np.float32).reshape(3, 4),
              (np.arange(3), np.arange(4.0)), [ev, ev],
              {"k": 1, "v": np.arange(2)},
              np.asarray(["x", "y"], object), np.float64(3.25), None,
              True, "s", 7, 2.5]
    for val in values:
        wire = json.loads(json.dumps(protocol.encode_value(val)))
        assert result_digest(protocol.decode_value(wire)) == \
            result_digest(val)


def test_digest_representation_independent():
    cat = Categorical.from_values(np.asarray(["a", "b", "a"], object))
    assert result_digest(cat) == result_digest(cat.to_strings())
    assert result_digest((1, 2)) == result_digest([1, 2])


def test_filter_roundtrip():
    f = (Filter("Name", "in", ["a", "b"]) & Filter("Process", "<", 4)) | \
        ~Filter("Event Type", "==", "Enter")
    wire = json.loads(json.dumps(protocol.encode_filter(f)))
    assert repr(protocol.decode_filter(wire)) == repr(f)


def test_custom_filter_subclass_rejected():
    class Weird(Filter):
        pass

    with pytest.raises(ProtocolError):
        protocol.encode_filter(Weird("Name", "==", "a"))


def test_callable_kwarg_rejected():
    with pytest.raises(ProtocolError):
        protocol.encode_value(lambda x: x)


def test_apply_steps_equals_direct_chain(pack_paths):
    trace = Trace.open(pack_paths[0])
    direct = (trace.query().slice_time(0.0, 40.0, trim="within")
              .filter(Filter("Process", "==", 0)).flat_profile())
    q = trace.query()
    wire = [{"k": "slice_time", "start": 0.0, "end": 40.0,
             "trim": "within"},
            {"k": "filter", "filter": protocol.encode_filter(
                Filter("Process", "==", 0))}]
    replayed = protocol.apply_steps(q, wire).flat_profile()
    assert result_digest(replayed) == result_digest(direct)


# ---------------------------------------------------------------------------
# per-op conformance: service result == direct library call, for every op
# ---------------------------------------------------------------------------

def test_every_trace_op_roundtrips(pack_paths, fresh_cache):
    trace = Trace.open(pack_paths)
    failures = []

    async def main():
        service = TraceService(max_handles=4)
        out = {}
        for op in registry.list_ops():
            if registry.get_op(op).scope != "trace":
                continue
            out[op] = await one(service, payload(pack_paths, op))
        return out

    responses = run(main())
    for op, resp in responses.items():
        wire = json.loads(json.dumps(resp["result"]))
        got = protocol.decode_value(wire)
        want = trace.query().run(op)
        if result_digest(got) != result_digest(want):
            failures.append(op)
        assert resp["digest"] == result_digest(want), op
    assert not failures


def test_every_set_op_roundtrips(pack_paths, fresh_cache):
    tset = TraceSet.open(pack_paths[:2])
    set_ops = [op for op in registry.list_ops()
               if registry.get_op(op).scope == "set"]
    assert set_ops

    async def main():
        service = TraceService(max_handles=4)
        out = {}
        for op in set_ops:
            out[op] = await one(
                service, set_payload(pack_paths[:2], op), set_scope=True)
        return out

    for op, resp in run(main()).items():
        got = protocol.decode_value(json.loads(json.dumps(resp["result"])))
        want = tset.query().run(op)
        assert result_digest(got) == result_digest(want), op


def test_trace_op_mapped_over_set(pack_paths, fresh_cache):
    async def main():
        service = TraceService()
        return await one(service,
                         set_payload(pack_paths[:2], "flat_profile"),
                         set_scope=True)

    got = protocol.decode_value(run(main())["result"])
    want = TraceSet.open(pack_paths[:2]).query().run("flat_profile")
    assert result_digest(got) == result_digest(want)


def test_streaming_digest_matches_eager(pack_paths, fresh_cache):
    async def main():
        service = TraceService()
        return await one(service, payload(pack_paths, "flat_profile",
                                          streaming=True))

    resp = run(main())
    want = Trace.open(pack_paths).query().flat_profile()
    assert resp["digest"] == result_digest(want)


# ---------------------------------------------------------------------------
# single-flight coalescing
# ---------------------------------------------------------------------------

def test_identical_inflight_plans_coalesce(pack_paths, fresh_cache,
                                           sleep_op):
    async def main():
        service = TraceService()
        body = payload(pack_paths[:1], sleep_op, cache=False,
                       kwargs={"duration": 0.05})
        results = await asyncio.gather(
            *[one(service, dict(body)) for _ in range(6)])
        return service, results

    service, results = run(main())
    assert service.counters["executed"] == 1
    assert service.counters["coalesced"] == 5
    digests = {r["digest"] for r in results}
    assert len(digests) == 1
    assert sum(1 for r in results if r.get("coalesced")) == 5


def test_distinct_plans_do_not_coalesce(pack_paths, fresh_cache, sleep_op):
    async def main():
        service = TraceService(per_tenant=8)
        bodies = [payload(pack_paths[:1], sleep_op, cache=False,
                          kwargs={"duration": 0.01, "tag": i})
                  for i in range(3)]
        results = await asyncio.gather(*[one(service, b) for b in bodies])
        return service, results

    service, results = run(main())
    assert service.counters["executed"] == 3
    assert service.counters["coalesced"] == 0
    assert len({r["digest"] for r in results}) == 3


def test_repeat_request_hits_shared_cache(pack_paths, fresh_cache):
    async def main():
        service = TraceService()
        body = payload(pack_paths, "flat_profile", streaming=True,
                       tenant="alice")
        first = await one(service, body)
        second = await one(service, dict(body))
        return service, first, second

    service, first, second = run(main())
    assert not first.get("cached")
    assert second.get("cached")
    assert first["digest"] == second["digest"]
    assert service.counters["cache_hits"] == 1
    assert plancache.stats()["tenants"]["alice"]["hits"] >= 1


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

def test_per_tenant_concurrency_rejects_floods(pack_paths, fresh_cache,
                                               sleep_op):
    async def main():
        service = TraceService(per_tenant=1, max_active=64)
        bodies = [payload(pack_paths[:1], sleep_op, cache=False,
                          tenant="greedy",
                          kwargs={"duration": 0.05, "tag": i})
                  for i in range(10)]
        results = await asyncio.gather(
            *[one(service, b) for b in bodies], return_exceptions=True)
        return service, results

    service, results = run(main())
    rejected = [r for r in results if isinstance(r, ServiceError)]
    ok = [r for r in results if isinstance(r, dict)]
    assert rejected and all(r.code == "tenant_saturated" for r in rejected)
    assert ok  # the in-limit requests still completed
    assert service.counters["rejected"] == len(rejected)
    assert service.tenant_counters["greedy"]["rejected"] == len(rejected)


def test_other_tenant_unaffected_by_flood(pack_paths, fresh_cache,
                                          sleep_op):
    async def main():
        service = TraceService(per_tenant=1, max_active=64)
        flood = [one(service, payload(
            pack_paths[:1], sleep_op, cache=False, tenant="greedy",
            kwargs={"duration": 0.05, "tag": i})) for i in range(8)]
        polite = one(service, payload(
            pack_paths[:1], sleep_op, cache=False, tenant="polite",
            kwargs={"duration": 0.01, "tag": 99}))
        results = await asyncio.gather(*flood, polite,
                                       return_exceptions=True)
        return results[-1]

    polite_result = run(main())
    assert isinstance(polite_result, dict) and polite_result["ok"]


def test_tenant_plan_cache_quota(pack_paths, fresh_cache):
    async def main():
        service = TraceService(tenant_quota=2)
        for i in range(5):
            await one(service, payload(
                pack_paths, "time_profile", streaming=True, tenant="alice",
                kwargs={"num_bins": 4 + i}))
        return service

    try:
        run(main())
        st = plancache.stats()
        assert st["tenant_quota"] == 2
        alice = st["tenants"]["alice"]
        assert alice["entries"] <= 2
        assert alice["evictions"] >= 3
    finally:
        plancache.configure(tenant_quota=0)


def test_interactive_lane_survives_bulk_saturation(pack_paths, fresh_cache,
                                                   sleep_op):
    """Starvation check: with the single bulk thread pinned by slow scans,
    an interactive query still completes on its reserved thread."""
    prev = set_scheduler(Scheduler(workers=2, interactive_workers=1))
    try:
        async def main():
            service = TraceService(per_tenant=8)
            bulk = [one(service, payload(
                pack_paths[:1], sleep_op, cache=False, lane="bulk",
                kwargs={"duration": 0.4, "tag": i})) for i in range(2)]
            bulk_tasks = [asyncio.ensure_future(b) for b in bulk]
            await asyncio.sleep(0.05)  # let bulk occupy its lane
            t0 = time.perf_counter()
            inter = await one(service, payload(
                pack_paths[1:2], sleep_op, cache=False, lane="interactive",
                kwargs={"duration": 0.01, "tag": 9}))
            latency = time.perf_counter() - t0
            await asyncio.gather(*bulk_tasks)
            return inter, latency

        inter, latency = run(main())
        assert inter["ok"]
        # the two 0.4 s bulk jobs serialize on the 1-thread bulk lane;
        # an interactive query that had to wait for it would take >0.35 s
        assert latency < 0.35
    finally:
        sched = set_scheduler(prev)
        if sched is not None:
            sched.shutdown()


# ---------------------------------------------------------------------------
# graceful shutdown
# ---------------------------------------------------------------------------

def test_drain_finishes_inflight_and_refuses_new(pack_paths, fresh_cache,
                                                 sleep_op):
    async def main():
        service = TraceService()
        slow = asyncio.ensure_future(one(service, payload(
            pack_paths[:1], sleep_op, cache=False,
            kwargs={"duration": 0.3})))
        await asyncio.sleep(0.05)
        drained = asyncio.ensure_future(service.drain(timeout=5))
        await asyncio.sleep(0.01)
        with pytest.raises(ServiceError) as exc:
            await one(service, payload(pack_paths[:1], "flat_profile"))
        slow_result = await slow
        return await drained, exc.value, slow_result

    drained, err, slow_result = run(main())
    assert drained is True
    assert err.status == 503 and err.code == "draining"
    assert slow_result["ok"]  # in-flight work finished, not cancelled


# ---------------------------------------------------------------------------
# handle pool
# ---------------------------------------------------------------------------

def test_handle_reopened_when_pack_rewritten(tmp_path, fresh_cache):
    out = tmp_path / "trc"
    big_trace(str(out), nprocs=1, events_per_proc=300, calls_per_iter=20,
              seed=1, format="pack")
    path = sorted(str(p) for p in out.glob("*.pack"))[0]

    async def main():
        service = TraceService()
        first = await one(service, payload([path], "flat_profile"))
        big_trace(str(out), nprocs=1, events_per_proc=300,
                  calls_per_iter=20, seed=2, format="pack")
        second = await one(service, payload([path], "flat_profile"))
        return service, first, second

    service, first, second = run(main())
    assert first["digest"] != second["digest"]
    assert service.handles.stats()["reopens"] == 1
    want = Trace.open(path).flat_profile()
    assert second["digest"] == result_digest(want)


def test_live_open_of_missing_shard_is_pending_not_404(tmp_path,
                                                      fresh_cache):
    """A live shard that has not appeared yet reads as empty: the pool
    keys it by the live-pending identity instead of answering 404."""
    p = str(tmp_path / "rank_0.pack")
    service = TraceService()
    out = run(service.live({"open": {"path": p, "mode": "live"},
                            "op": "flat_profile", "tenant": "t"}))
    assert out["ok"] and out["watermark"]["rows"] == 0
    (h,) = service.handles._handles.values()
    assert h.kind == "live" and h.ident == ("live-pending", p)


def test_handle_pool_lru_bound(pack_paths, fresh_cache):
    async def main():
        service = TraceService(max_handles=2)
        for p in pack_paths[:3]:
            await one(service, payload([p], "flat_profile"))
        return service.handles.stats()

    st = run(main())
    assert st["open"] == 2
    assert st["evictions"] == 1


# ---------------------------------------------------------------------------
# request validation
# ---------------------------------------------------------------------------

def test_unknown_op_and_bad_requests(pack_paths, fresh_cache):
    async def main():
        service = TraceService()
        with pytest.raises(ProtocolError):
            await one(service, payload(pack_paths[:1], "no_such_op"))
        with pytest.raises(ProtocolError):
            await one(service, {"op": "flat_profile"})  # no open spec
        with pytest.raises(ProtocolError):
            # set-scope op on the single-trace endpoint
            await one(service, payload(pack_paths[:1], "diff_flat_profile"))
        with pytest.raises(ServiceError) as exc:
            await one(service, payload(["/no/such/file.pack"],
                                       "flat_profile"))
        assert exc.value.status == 404

    run(main())


# ---------------------------------------------------------------------------
# HTTP server + client round trip
# ---------------------------------------------------------------------------

def test_http_client_roundtrip(pack_paths, fresh_cache):
    local = Trace.open(pack_paths).query().flat_profile()
    windowed_local = (Trace.open(pack_paths[0]).query()
                      .slice_time(0.0, 30.0, trim="within").time_profile())

    async def main():
        server = await TraceServer(TraceService(), port=0).start()

        def client_work():
            with ServiceClient("127.0.0.1", server.port,
                               tenant="alice") as c:
                assert c.health()["ok"]
                assert {o["name"] for o in c.ops()} >= {"flat_profile",
                                                        "diff_flat_profile"}
                trace = c.open(pack_paths, streaming=True)
                prof = trace.query().flat_profile()
                w = (c.open(pack_paths[0]).query()
                     .slice_time(0.0, 30.0, trim="within").time_profile())
                digest = trace.query().flat_profile(digest_only=True)
                with pytest.raises(RemoteError) as exc:
                    trace.query().run("no_such_op")
                assert exc.value.status == 400
                stats = c.stats()
                return prof, w, digest, stats

        result = await asyncio.to_thread(client_work)
        await server.shutdown(grace=5)
        return result

    prof, w, digest, stats = run(main())
    assert result_digest(prof) == result_digest(local)
    assert result_digest(w) == result_digest(windowed_local)
    assert digest == result_digest(local)
    assert stats["service"]["requests"] >= 4
    assert "alice" in stats["tenants"]


def test_http_setquery_roundtrip(pack_paths, fresh_cache):
    local = TraceSet.open(pack_paths[:2]).query().run("diff_flat_profile")

    async def main():
        server = await TraceServer(TraceService(), port=0).start()

        def client_work():
            with ServiceClient("127.0.0.1", server.port) as c:
                tset = c.open_set(pack_paths[:2])
                return tset.query().diff_flat_profile()

        got = await asyncio.to_thread(client_work)
        await server.shutdown(grace=5)
        return got

    got = run(main())
    assert result_digest(got) == result_digest(local)


# ---------------------------------------------------------------------------
# /diagnose: the diagnostics suite through the service
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pathology_pack(tmp_path_factory):
    from repro.readers.pack import write_pack
    from repro.tracegen import pathology_trace
    tr, gt = pathology_trace("straggler", nprocs=3, iters=12,
                             magnitude=2.0, seed=4)
    p = str(tmp_path_factory.mktemp("diag_serve") / "patho.pack")
    write_pack(tr, p)
    return p, gt


def test_diagnose_endpoint_digest_equals_library(pathology_pack,
                                                 fresh_cache):
    path, gt = pathology_pack
    local = Trace.open(path).query().run("diagnose", cache=False)

    async def main():
        server = await TraceServer(TraceService(), port=0).start()

        def client_work():
            with ServiceClient("127.0.0.1", server.port, tenant="t") as c:
                trace = c.open(path)
                via_endpoint = trace.diagnose()
                via_query = trace.query().diagnose()
                subset = trace.diagnose(detectors=["stragglers"])
                return via_endpoint, via_query, subset

        result = await asyncio.to_thread(client_work)
        await server.shutdown(grace=5)
        return result

    via_endpoint, via_query, subset = run(main())
    assert result_digest(via_endpoint) == result_digest(local)
    assert result_digest(via_query) == result_digest(local)
    assert result_digest(subset) == result_digest(
        Trace.open(path).query().run("diagnose",
                                     detectors=["stragglers"], cache=False))
    # the served frame still names the injected culprit at top-1
    assert str(via_endpoint["detector"][0]) != ""
    f = subset
    assert int(f["process"][0]) == gt.process


def test_diagnose_requests_coalesce_and_cache(pathology_pack, fresh_cache):
    path, _ = pathology_pack

    async def main():
        service = TraceService()
        body = payload([path], "diagnose")
        results = await asyncio.gather(
            *[one(service, dict(body)) for _ in range(5)])
        again = await one(service, dict(body))
        return service, results, again

    service, results, again = run(main())
    # 5 identical in-flight diagnose plans -> 1 execution
    assert service.counters["executed"] == 1
    assert service.counters["coalesced"] == 4
    assert len({r["digest"] for r in results}) == 1
    # and a later identical request is a plan-cache hit
    assert again.get("cached")
    assert again["digest"] == results[0]["digest"]


def test_detector_ops_directly_callable(pathology_pack, fresh_cache):
    """Individual detectors are ordinary registered ops on the service."""
    path, gt = pathology_pack

    async def main():
        service = TraceService()
        return await one(service, payload(
            [path], "stragglers", kwargs={"threshold": 0.1}))

    resp = run(main())
    want = Trace.open(path).query().run("stragglers", threshold=0.1)
    assert resp["digest"] == result_digest(want)


def test_patterns_ops_through_plan_and_service(pack_paths, fresh_cache):
    """activity_series / detect_pattern are registered ops: callable as
    lazy-plan terminals and remotely through the service, with identical
    digests."""
    for op, kwargs in (("activity_series", {"num_bins": 64}),
                       ("detect_pattern", {"num_bins": 32,
                                           "max_patterns": 4})):
        assert registry.get_op(op) is not None, op
        local = Trace.open(pack_paths).query().run(op, **kwargs)

        async def main():
            service = TraceService()
            return await one(service, payload(pack_paths, op,
                                              kwargs=dict(kwargs)))

        resp = run(main())
        assert resp["digest"] == result_digest(local), op
        wire = protocol.decode_value(json.loads(json.dumps(resp["result"])))
        assert result_digest(wire) == result_digest(local), op


def test_breaker_recovers_after_repair(tmp_path, fresh_cache):
    """Circuit-breaker recovery: a pack whose opens trip the breaker keeps
    fast-failing 422 only until the operator repairs it — after the
    cooldown the half-open probe sees the repaired file and the breaker
    closes (it must not serve 422s forever)."""
    import os
    import subprocess
    import sys

    out = tmp_path / "trc"
    big_trace(str(out), nprocs=1, events_per_proc=300, calls_per_iter=20,
              seed=3, format="pack")
    path = sorted(str(p) for p in out.glob("*.pack"))[0]
    good = Trace.open(path).flat_profile()

    # damage: tear off the footer AND the tail of the last chunk group so
    # the strict open raises
    data = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(data[: int(len(data) * 0.6)])

    async def main():
        service = TraceService(breaker_threshold=2, breaker_cooldown=30.0)
        codes = []
        for _ in range(4):
            try:
                await one(service, payload([path], "flat_profile"))
                codes.append("ok")
            except ServiceError as e:
                codes.append((e.status, e.code))
        assert codes[1] == (422, "source_corrupt")    # breaker tripped
        assert codes[3] == (422, "source_corrupt")    # fast-fail, no open
        st = service.handles.stats()
        assert st["breaker_trips"] == 1
        assert st["breaker_fastfails"] >= 1
        assert st["breaker_open"] == 1

        # operator repairs the pack with the CLI, atomically swapping the
        # salvaged rewrite into place
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        fixed = path + ".fixed"
        proc = subprocess.run(
            [sys.executable, os.path.join(repo, "tools", "pack.py"),
             "--repair", path, "-o", fixed],
            capture_output=True, text=True, cwd=repo)
        assert proc.returncode == 0, proc.stderr
        os.replace(fixed, path)

        # before the cooldown lapses the breaker still fast-fails —
        # repair does not bypass the half-open schedule
        try:
            await one(service, payload([path], "flat_profile"))
            probed_early = True
        except ServiceError as e:
            probed_early = False
            assert e.code == "source_corrupt"
        assert not probed_early

        # cooldown lapses (aged directly rather than sleeping it out)
        for b in service.handles._fails.values():
            b["until"] = 0.0
        res = await one(service, payload([path], "flat_profile"))
        assert res["ok"]                              # probe closed it
        res2 = await one(service, payload([path], "flat_profile",
                                          kwargs={}))
        assert res2["ok"]
        assert service.handles.stats()["breaker_open"] == 0
        return res

    res = run(main())
    # the repaired pack serves the salvageable prefix: same op, fewer or
    # equal rows than the pristine original
    assert res["digest"] != "" and len(good) > 0
