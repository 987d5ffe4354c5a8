"""End-to-end smoke of the accelerator analysis path on one TPU chip.

    python chip_smoke.py [--events N] [--seed S]

Phases, one line each on stdout:

* data     — a seeded 256-rank pack trace written with
             ``tracegen.big_trace`` (``--events``, default 10,240,000, each
             rank rounded to whole iterations); event and record counts.
* library  — the six ``backend="pallas"`` ops on a ``Trace.open(...,
             streaming=True)`` handle against the ``numpy`` reference:
             counts exact, sums within the f32 tolerance of docs/kernels.md.
* compiled — one ``repro.kernels.ops`` wrapper lowered at the shapes used
             above must hold a Mosaic ``tpu_custom_call`` (not interpret).
* parallel — a ``processes=2`` handle on the same files; its spawned
             workers stay off the chip and its digest equals the serial one.
* served   — an in-process ``TraceServer``; ``flat_profile`` and
             ``comm_matrix`` over HTTP digest-equal the library results.

The last line is ``{"ok": true, "device": {...}}``.  The script exits
non-zero, without that line, when JAX's default backend is not a TPU or
when any phase fails.  Seconds printed are this script's host wall-clock
times (first calls include compilation), not device metrics.

All work sits under ``__main__``: spawned workers re-import this file.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
NPROCS = 256


def phase(name: str, **fields) -> None:
    print(f"{name:9s} " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def data_phase(tmp: str, events: int, seed: int):
    from repro import tracegen
    from repro.readers.pack import read_footer
    paths, gen_s = timed(lambda: tracegen.big_trace(
        tmp, nprocs=NPROCS, events_per_proc=events // NPROCS, seed=seed,
        format="pack"))
    footers = [read_footer(p) for p in paths]
    n_events = sum(int(f["rows"]) for f in footers)
    n_names = len(set().union(*(f["names"] for f in footers)))
    return paths, n_events, n_names, gen_s


def library_phase(paths, n_events, gen_s):
    from benchmarks.bench_backends import OP_CASES, tolerant_equal
    from repro.core.trace import Trace
    from repro.serving.protocol import result_digest

    handle = Trace.open(paths, streaming=True, cache=False)
    ref = {op: timed(lambda: handle.query().run(op, backend="numpy", **kw))
           for op, kw in OP_CASES.items()}
    numpy_s = {op: s for op, (_, s) in ref.items()}
    ref = {op: r for op, (r, _) in ref.items()}
    calls = int(sum(ref["flat_profile"]["count"]))
    msgs = int(ref["message_histogram"][0].sum())
    phase("data", events=n_events, ranks=len(paths), call_records=calls,
          messages=msgs, gen_host_s=round(gen_s, 3))

    got = {}
    for op, kw in OP_CASES.items():
        res, first_s = timed(lambda: handle.query().run(
            op, backend="pallas", **kw))
        again, warm_s = timed(lambda: handle.query().run(
            op, backend="pallas", **kw))
        if result_digest(again) != result_digest(res):
            raise AssertionError(f"{op}: warm pallas call changed result")
        if not tolerant_equal(op, ref[op], res):
            raise AssertionError(f"{op}: pallas result differs from numpy")
        got[op] = res
        phase("library", op=op, matches_numpy=True,
              first_call_host_s=round(first_s, 3),
              warm_call_host_s=round(warm_s, 3),
              numpy_call_host_s=round(numpy_s[op], 3))
    return got, calls


def compiled_phase(calls: int, n_seg: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core.accel import block_size
    from repro.kernels.ops import segment_sum_matrix
    text = segment_sum_matrix.lower(
        jax.ShapeDtypeStruct((calls,), jnp.int32),
        jax.ShapeDtypeStruct((2, calls), jnp.float32), n_seg=n_seg,
        be=block_size(calls, n_seg)).compile().as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError("segment_sum_matrix compiled without a Mosaic "
                             "kernel: the kernels are being interpreted")
    phase("compiled", wrapper="segment_sum_matrix", records=calls,
          n_seg=n_seg, tpu_custom_call=True)


def parallel_phase(paths, got) -> None:
    from benchmarks.bench_backends import OP_CASES
    from repro.core.trace import Trace
    from repro.serving.protocol import result_digest
    par = Trace.open(paths, streaming=True, cache=False, processes=2)
    res, s = timed(lambda: par.query().run(
        "flat_profile", backend="pallas", **OP_CASES["flat_profile"]))
    if result_digest(res) != result_digest(got["flat_profile"]):
        raise AssertionError("processes=2 flat_profile digest differs from "
                             "the serial streaming result")
    phase("parallel", op="flat_profile", processes=2, digest_equal=True,
          host_s=round(s, 3))


def served_phase(paths, got) -> None:
    import asyncio

    from benchmarks.bench_backends import OP_CASES
    from repro.serving.client import ServiceClient
    from repro.serving.protocol import result_digest
    from repro.serving.tracequery import TraceServer, TraceService

    ops = ("flat_profile", "comm_matrix")

    async def main():
        server = await TraceServer(TraceService(), port=0).start()

        def client_work():
            with ServiceClient("127.0.0.1", server.port, timeout=900) as c:
                trace = c.open(paths, streaming=True)
                return {op: timed(lambda: trace.query().run(
                    op, backend="pallas", digest_only=True,
                    **OP_CASES[op])) for op in ops}

        try:
            return await asyncio.to_thread(client_work)
        finally:
            await server.shutdown(grace=5)

    for op, (digest, s) in asyncio.run(main()).items():
        if digest != result_digest(got[op]):
            raise AssertionError(f"served {op} digest differs from library")
        phase("served", op=op, digest_equal=True, host_s=round(s, 3))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--events", type=int, default=10_240_000,
                    help="trace size (events over 256 ranks)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX's default backend is "
              f"{jax.default_backend()!r}, not 'tpu'", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    import tempfile
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        paths, n_events, n_names, gen_s = data_phase(tmp, args.events,
                                                     args.seed)
        got, calls = library_phase(paths, n_events, gen_s)
        compiled_phase(calls, n_seg=n_names)
        parallel_phase(paths, got)
        served_phase(paths, got)

    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
