import os
os.environ["XLA_FLAGS"] = (os.environ.get("_DRYRUN_BASE_XLA", "")
                           + " --xla_force_host_platform_device_count=512").strip()
# ^ MUST run before any other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (architecture × shape) cell on the
production mesh and record memory/cost/collective statistics.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-0.5b \
        --shape train_4k [--multi-pod] [--out experiments/dryrun]

    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Each cell writes ``<out>/<arch>__<shape>__<mesh>.json`` with
``memory_analysis()``, ``cost_analysis()``, the parsed per-device collective
wire bytes, and the three-term roofline — the artifacts EXPERIMENTS.md
§Dry-run/§Roofline and ``benchmarks/roofline.py`` read.  ``--save-hlo`` also
dumps the partitioned HLO for the Pipit HLO reader.
"""

import argparse
import gzip
import json
import time
import traceback

import jax
import jax.numpy as jnp

from ..analysis.hlostats import collective_stats
from ..analysis.roofline import roofline_terms
from ..configs import ARCH_NAMES, get_config
from ..models.config import SHAPES
from .mesh import make_production_mesh
from .steps import build_cell

SKIP = {
    # long_500k needs a bounded cache: pure full-attention archs are excluded
    # by the assignment (see DESIGN.md §Shape skips)
    ("whisper-medium", "long_500k"),
    ("qwen2-moe-a2.7b", "long_500k"),
    ("qwen3-moe-235b-a22b", "long_500k"),
    ("qwen1.5-110b", "long_500k"),
    ("qwen1.5-0.5b", "long_500k"),
    ("codeqwen1.5-7b", "long_500k"),
    ("phi-3-vision-4.2b", "long_500k"),
}


def _cell_costs(cfg, shape, mesh, chips):
    """Compile one program and pull (flops, bytes, wire_bytes) — all
    per-device (XLA SPMD cost analysis reports per-partition numbers)."""
    cell = build_cell(cfg, shape, mesh)
    compiled = cell.lower(mesh).compile()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_stats(hlo, default_group=chips)
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            float(coll["total"]["wire_bytes"]), cell, compiled, coll, cost)


def _copies(u: int, T: int) -> int:
    """How many scan-body copies XLA's cost model sees at unroll=u, trip=T."""
    return T if T <= u else u + (T % u)


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             save_hlo: bool = False, overrides=None) -> dict:
    """Three compiles per cell:

    * the *deployment* program (layer scan, unroll=1) → memory analysis,
      collective schedule, saved HLO;
    * two *cost probes* (inner scans fully unrolled; layer scan unroll 1 / 2)
      → exact per-layer FLOPs/bytes/wire-bytes, because XLA's cost model
      counts a scan body once regardless of trip count (measured; see
      EXPERIMENTS.md §Methodology).  Corrected totals:
          body = (F(u2) − F(u1)) / (copies(2,T) − 1)
          F*   = F(u1) + (T − 1) · body
    """
    import dataclasses as dc
    cfg = get_config(arch)
    if overrides:
        cfg = dc.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"

    t0 = time.time()
    f_main, b_main, w_main, cell, compiled, coll, cost = _cell_costs(
        cfg, shape, mesh, chips)
    t_main = time.time() - t0

    from ..models import build_model
    T = build_model(cfg).n_periods
    t0 = time.time()
    f1, b1, w1, *_ = _cell_costs(dc.replace(cfg, cost_probe=1), shape, mesh,
                                 chips)
    if T > 1:
        f2, b2, w2, *_ = _cell_costs(dc.replace(cfg, cost_probe=2), shape,
                                     mesh, chips)
        dc2 = _copies(2, T) - 1
        flops = f1 + (T - 1) * (f2 - f1) / dc2
        hbm_bytes = b1 + (T - 1) * (b2 - b1) / dc2
        wire = w1 + (T - 1) * (w2 - w1) / dc2
    else:
        flops, hbm_bytes, wire = f1, b1, w1
    t_probe = time.time() - t0

    mem = compiled.memory_analysis()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    n_active = cell.meta["active_params"]
    model_flops = (6 if shape.kind == "train" else 2) * n_active * tokens

    rl = roofline_terms(flops * chips, hbm_bytes * chips, wire, chips,
                        model_flops)
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "chips": chips,
        "kind": shape.kind, "n_periods": T,
        "compile_s": round(t_main, 2), "probe_s": round(t_probe, 2),
        "params": cell.meta["params"], "active_params": n_active,
        "memory_analysis": {
            "argument_size": getattr(mem, "argument_size_in_bytes", None),
            "output_size": getattr(mem, "output_size_in_bytes", None),
            "temp_size": getattr(mem, "temp_size_in_bytes", None),
            "peak_size": (getattr(mem, "argument_size_in_bytes", 0) or 0)
                         + (getattr(mem, "temp_size_in_bytes", 0) or 0),
        },
        "cost_analysis_raw": {k: float(v) for k, v in cost.items()
                              if isinstance(v, (int, float))},
        "per_device": {"flops": flops, "hbm_bytes": hbm_bytes,
                       "wire_bytes": wire},
        "collectives_schedule": coll,
        "roofline": rl,
        "rules": {k: list(v) if isinstance(v, tuple) else v
                  for k, v in cell.meta["rules"].items()},
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        base = f"{arch}__{shape_name}__{mesh_name}"
        with open(os.path.join(out_dir, base + ".json"), "w") as f:
            json.dump(record, f, indent=1)
        if save_hlo:
            with gzip.open(os.path.join(out_dir, base + ".hlo.gz"), "wt") as f:
                f.write(compiled.as_text())
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_NAMES:
            if a == "pipit-lm-100m":
                continue
            for s in SHAPES:
                if (a, s) not in SKIP:
                    cells.append((a, s))
    else:
        cells = [(args.arch, args.shape)]

    mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
    failures = []
    for arch, shape in cells:
        base = os.path.join(args.out, f"{arch}__{shape}__{mesh_name}.json")
        if args.skip_existing and os.path.exists(base):
            print(f"[skip] {arch} {shape} (exists)")
            continue
        try:
            r = run_cell(arch, shape, args.multi_pod, args.out, args.save_hlo)
            rl = r["roofline"]
            print(f"[ok] {arch:22s} {shape:12s} {mesh_name} "
                  f"compile={r['compile_s']:.1f}s "
                  f"compute={rl['compute_s']:.3e}s mem={rl['memory_s']:.3e}s "
                  f"coll={rl['collective_s']:.3e}s → {rl['bottleneck']}",
                  flush=True)
        except Exception as e:
            failures.append((arch, shape, repr(e)))
            print(f"[FAIL] {arch} {shape}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: {failures}")
    print("dry-run complete.")


if __name__ == "__main__":
    main()
