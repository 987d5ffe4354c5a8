"""Roofline table builder.

Two modes:

* default — reads the dry-run artifacts and renders the EXPERIMENTS.md
  §Roofline table (one row per arch × shape × mesh).
* ``--ops`` — **op-bandwidth roofline** for the analysis-op backend
  registry: generates a pack-suite trace at ``--events`` scale, runs every
  registered backend of every kernel-backed op, and reports achieved vs.
  peak bytes/s (peak = a measured host STREAM-copy rate; on a real TPU the
  HBM roofline applies instead).  ``--json`` writes the records for CI
  artifact upload.

Run standalone::

    PYTHONPATH=src python -m benchmarks.roofline [--ops] [--events N]
        [--json PATH]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time
from typing import Dict, List

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "dryrun")

# single source of truth for the dry-run roofline table: artifact key →
# rendered column header, in display order (the row builder below is
# checked against it, so the two can no longer drift apart)
COLUMNS = (
    ("arch", "arch"),
    ("shape", "shape"),
    ("mesh", "mesh"),
    ("bottleneck", "bound"),
    ("compute_s", "compute[s]"),
    ("memory_s", "memory[s]"),
    ("collective_s", "collective[s]"),
    ("step_time_s", "step≥[s]"),
    ("useful_flop_frac", "useful/HLO"),
    ("mfu_bound", "MFU-bound"),
    ("peak_gib_per_dev", "peak GiB/dev"),
)
COLS = tuple(key for key, _hdr in COLUMNS)

DEFAULT_OPS_EVENTS = int(os.environ.get("ROOFLINE_OPS_EVENTS", 10_000_000))
OPS_NPROCS = 8

# bytes each backend must stream per record at minimum: the canonical
# record fields the kernels consume (see docs/kernels.md) — call-record
# ops read (start, end, proc, code, value) f64/i64, comm_matrix reads
# (src, dst, size, ts), message_histogram just the sizes
OP_RECORD_BYTES = {
    "flat_profile": 40,
    "time_profile": 40,
    "load_imbalance": 40,
    "stragglers": 40,
    "comm_matrix": 32,
    "message_histogram": 8,
}


def load_records(art_dir: str = ART_DIR) -> List[Dict]:
    out = []
    for p in sorted(glob.glob(os.path.join(art_dir, "*.json"))):
        with open(p) as f:
            out.append(json.load(f))
    return out


def fmt(x, nd=3):
    if isinstance(x, float):
        return f"{x:.3e}" if (abs(x) < 1e-2 or abs(x) > 1e4) else f"{x:.3f}"
    return str(x)


def table(records: List[Dict], mesh: str = None) -> str:
    rows = []
    for r in records:
        if mesh and r["mesh"] != mesh:
            continue
        rl = r["roofline"]
        mem = r["memory_analysis"]
        cells = {
            "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
            "bottleneck": rl["bottleneck"],
            "compute_s": fmt(rl["compute_s"]),
            "memory_s": fmt(rl["memory_s"]),
            "collective_s": fmt(rl["collective_s"]),
            "step_time_s": fmt(rl["step_time_s"]),
            "useful_flop_frac": f"{rl.get('useful_flop_frac', 0):.3f}",
            "mfu_bound": f"{rl.get('mfu_bound', 0) * 100:.2f}%",
            "peak_gib_per_dev": f"{(mem['peak_size'] or 0) / 2**30:.2f}",
        }
        rows.append([cells[key] for key in COLS])
    hdr = [h for _key, h in COLUMNS]
    lines = ["| " + " | ".join(hdr) + " |",
             "|" + "|".join(["---"] * len(hdr)) + "|"]
    for row in rows:
        lines.append("| " + " | ".join(row) + " |")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# --ops: analysis-op backend bandwidth roofline
# ---------------------------------------------------------------------------

def measured_peak_bytes_s() -> float:
    """Host memory-bandwidth ceiling: best of a few big STREAM-style copies
    (read + write counted, like STREAM's Copy kernel)."""
    import numpy as np
    a = np.random.default_rng(0).random(1 << 25)  # 256 MiB
    b = np.empty_like(a)
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        np.copyto(b, a)
        best = max(best, 2 * a.nbytes / (time.perf_counter() - t0))
    return best


def _ops_trace(events: int, tmp: str):
    """A packed straggler trace near ``events`` rows (the 10M-event pack
    suite of the detector benchmarks), opened eagerly."""
    from repro.core.trace import Trace
    from repro.readers.pack import write_pack
    from repro.tracegen import baseline, pathology_trace

    probe = baseline(nprocs=OPS_NPROCS, iters=8, seed=0)
    per_iter = max(1.0, len(probe.events) / 8.0)
    iters = max(16, int(round(events / per_iter)))
    tr, _gt = pathology_trace("straggler", nprocs=OPS_NPROCS, iters=iters,
                              magnitude=2.0, seed=0)
    pack = os.path.join(tmp, "roofline_ops.pack")
    write_pack(tr, pack)
    return Trace.open(pack)


def op_bandwidth(events: int = DEFAULT_OPS_EVENTS) -> Dict:
    """Achieved vs. peak bytes/s for every registered backend of every
    kernel-backed op at ``events`` scale."""
    import jax
    import numpy as np
    from repro.core import registry
    from repro.core.constants import ENTER, ET, MPI_SEND, NAME

    peak = measured_peak_bytes_s()
    rows = []
    with tempfile.TemporaryDirectory() as tmp:
        trace = _ops_trace(events, tmp)
        ev = trace.events
        is_enter = ev.cat(ET).mask_eq(ENTER)
        match = np.asarray(ev.column("_matching_event"), np.int64)
        n_calls = int((is_enter & (match >= 0)).sum())
        n_sends = int(ev.cat(NAME).mask_eq(MPI_SEND).sum())
        n_records = {"comm_matrix": n_sends, "message_histogram": n_sends}
        q = trace.query()
        for op in sorted(OP_RECORD_BYTES):
            backends = registry.list_backends(op)
            nrec = n_records.get(op, n_calls)
            nbytes = nrec * OP_RECORD_BYTES[op]
            for b in backends:
                t0 = time.perf_counter()
                q.run(op, cache=False, backend=b)
                wall = time.perf_counter() - t0
                rows.append({
                    "op": op, "backend": b, "records": nrec,
                    "bytes": nbytes, "wall_s": round(wall, 3),
                    "achieved_gib_s": round(nbytes / wall / 2**30, 3),
                    "frac_of_peak": round(nbytes / wall / peak, 6),
                })
        n_events = len(ev)
    return {"mode": "op_bandwidth", "events": n_events,
            "nprocs": OPS_NPROCS, "peak_gib_s": round(peak / 2**30, 2),
            # the kernels compile on a TPU and are interpreted elsewhere
            "interpret_mode": jax.default_backend() != "tpu",
            "rows": rows, "ok": True}


def ops_table(report: Dict) -> str:
    hdr = ["op", "backend", "records", "wall[s]", "achieved GiB/s",
           "peak GiB/s", "% of peak"]
    lines = ["| " + " | ".join(hdr) + " |",
             "|" + "|".join(["---"] * len(hdr)) + "|"]
    for r in report["rows"]:
        lines.append(
            f"| {r['op']} | {r['backend']} | {r['records']} "
            f"| {r['wall_s']:.3f} | {r['achieved_gib_s']:.3f} "
            f"| {report['peak_gib_s']:.1f} "
            f"| {r['frac_of_peak'] * 100:.3f}% |")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ops", action="store_true",
                    help="op-backend bandwidth roofline instead of the "
                         "dry-run table")
    ap.add_argument("--events", type=int, default=DEFAULT_OPS_EVENTS,
                    help="trace size for --ops (default %(default)s)")
    ap.add_argument("--json", default=None,
                    help="also write the --ops records to this path")
    args = ap.parse_args(argv)

    if args.ops:
        report = op_bandwidth(args.events)
        print(f"# Op-backend bandwidth — {report['events']} events, "
              f"interpret={report['interpret_mode']}\n")
        print(ops_table(report))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(report, f, indent=2)
            print(f"\nwrote {args.json}")
        return

    recs = load_records()
    if not recs:
        print("no dry-run artifacts found — run repro.launch.dryrun first")
        return
    print(f"# Roofline — BASELINE ({len(recs)} cells)\n")
    for mesh in ("pod16x16", "pod2x16x16"):
        sub = [r for r in recs if r["mesh"] == mesh]
        if sub:
            print(f"\n## mesh {mesh} ({len(sub)} cells)\n")
            print(table(sub))
    # bottleneck census
    census: Dict[str, int] = {}
    for r in recs:
        census[r["roofline"]["bottleneck"]] = census.get(
            r["roofline"]["bottleneck"], 0) + 1
    print("\nbottleneck census:", census)

    opt_dir = os.path.join(os.path.dirname(__file__), "..", "experiments",
                           "dryrun_opt")
    opt = load_records(opt_dir) if os.path.isdir(opt_dir) else []
    if opt:
        print(f"\n# Roofline — OPTIMIZED archs after §Perf ({len(opt)} cells)\n")
        print(table(opt))
        base = {(r["arch"], r["shape"], r["mesh"]): r for r in recs}
        print("\n## step-bound improvement vs baseline\n")
        for r in opt:
            b = base.get((r["arch"], r["shape"], r["mesh"]))
            if b:
                s0 = b["roofline"]["step_time_s"]
                s1 = r["roofline"]["step_time_s"]
                print(f"  {r['arch']:22s} {r['shape']:12s} {r['mesh']:10s} "
                      f"{s0:9.3f}s → {s1:9.3f}s  ({s0 / max(s1, 1e-12):5.2f}×)")


if __name__ == "__main__":
    main()
