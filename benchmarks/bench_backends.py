"""Backend-registry parity smoke: every registered op backend against the
numpy reference at scale, digest-checked across execution paths.

For every op with a registered ``pallas`` backend (flat_profile,
time_profile, load_imbalance, comm_matrix, message_histogram, stragglers):

* **numerics gate** — the pallas result must agree with the exact numpy
  result to f32 rounding (``rtol=1e-4`` plus an absolute tolerance scaled
  to the result's largest magnitude, since f32 accumulation error follows
  the accumulated mass, not a cell's net value);
  ``message_histogram`` counts must be *exactly* equal.
* **path gate** — the pallas result must be digest-identical between the
  eager pack path and the out-of-core streaming path (the canonical-order
  contract of docs/kernels.md).

Run standalone::

    PYTHONPATH=src python -m benchmarks.bench_backends [--events N]
        [--json PATH]

or as part of ``python -m benchmarks.run`` (the ``--events`` knob is
forwarded).  ``BENCH_BACKENDS_EVENTS`` overrides the default scale.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

DEFAULT_EVENTS = int(os.environ.get("BENCH_BACKENDS_EVENTS", 1_000_000))
NPROCS = 8
CHUNK_ROWS = 250_000

# op → kwargs for one representative invocation per op
OP_CASES = {
    "flat_profile": {"metrics": ("time.exc", "time.inc")},
    "time_profile": {"num_bins": 32},
    "load_imbalance": {},
    "comm_matrix": {},
    "message_histogram": {"bins": 16},
    "stragglers": {},
}


def _iters_for(events: int, nprocs: int) -> int:
    from repro.tracegen import baseline
    probe = baseline(nprocs=nprocs, iters=8, seed=0)
    per_iter = max(1.0, len(probe.events) / 8.0)
    return max(16, int(round(events / per_iter)))


def tolerant_equal(op, a, b) -> bool:
    """pallas vs numpy: f32 rounding on sums, exact everywhere else.

    f32 accumulation error scales with the *accumulated magnitude*, not a
    cell's net value (a nearly-empty time-profile cell next to a full one
    carries the full bin's rounding), so the absolute tolerance is scaled
    by the result's largest float value."""
    if op == "comm_matrix":
        scale = max(float(np.abs(a).max()), 1.0)
        return bool(np.allclose(a, b, rtol=1e-4, atol=1e-6 * scale))
    if op == "message_histogram":
        return bool((a[0] == b[0]).all() and (a[1] == b[1]).all())
    if list(a.columns) != list(b.columns) or len(a) != len(b):
        return False
    scale = 1.0
    for c in a.columns:
        va = np.asarray(a[c])
        if va.dtype.kind == "f" and len(va):
            scale = max(scale, float(np.abs(va).max()))
    for c in a.columns:
        va, vb = np.asarray(a[c]), np.asarray(b[c])
        if va.dtype.kind == "f":
            if not np.allclose(va, vb, rtol=1e-4, atol=1e-6 * scale):
                return False
        elif va.dtype == object:
            if not all(x == y for x, y in zip(va, vb)):
                return False
        elif not (va == vb).all():
            return False
    return True


def bench(events: int = DEFAULT_EVENTS) -> dict:
    from repro.core import registry
    from repro.core.trace import Trace
    from repro.readers.pack import write_pack
    from repro.serving.protocol import result_digest
    from repro.tracegen import pathology_trace

    iters = _iters_for(events, NPROCS)
    tr, _gt = pathology_trace("straggler", nprocs=NPROCS, iters=iters,
                              magnitude=2.0, seed=0)
    out = {"events": len(tr.events), "nprocs": NPROCS, "ops": {}, "ok": True}
    with tempfile.TemporaryDirectory() as tmp:
        pack = os.path.join(tmp, "backends.pack")
        write_pack(tr, pack)
        eager = Trace.open(pack)
        stream = Trace.open(pack, streaming=True, chunk_rows=CHUNK_ROWS)
        for op, kwargs in OP_CASES.items():
            backends = registry.list_backends(op)
            ref = eager.query().run(op, cache=False, backend="numpy",
                                    **kwargs)
            rec = {"backends": backends}
            for b in backends:
                if b == "numpy":
                    continue
                t0 = time.perf_counter()
                res = eager.query().run(op, cache=False, backend=b,
                                        **kwargs)
                rec[f"{b}_eager_s"] = round(time.perf_counter() - t0, 3)
                rec[f"{b}_matches_numpy"] = tolerant_equal(op, ref, res)
                t0 = time.perf_counter()
                sres = stream.query().run(op, cache=False, backend=b,
                                          **kwargs)
                rec[f"{b}_stream_s"] = round(time.perf_counter() - t0, 3)
                rec[f"{b}_digest_identical"] = (
                    result_digest(res) == result_digest(sres))
                out["ok"] = (out["ok"] and rec[f"{b}_matches_numpy"]
                             and rec[f"{b}_digest_identical"])
            out["ops"][op] = rec
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--events", type=int, default=DEFAULT_EVENTS)
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    res = bench(args.events)
    print(json.dumps(res, indent=1, default=str))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(res, f, indent=2, default=str)
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
