"""Compile, for a TPU v5e that is described and not attached, every kernel
shape the benchmark's cells reach.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/compile_rehearsal.py \
        [--seed N] [--workload NAME ...]

For each cell of ``BENCHMARK.json`` it derives, from the configuration's
ground truth at ``--seed`` (no pack is written), the record counts each op
hands its kernel: the whole trace's calls and sends.  Each shape is
compiled with ``interpret=False`` at the block size
``repro.core.accel.block_size`` picks.  One line per shape: kernel,
records, output rows, block, seconds, and ``ok`` or the compiler's
error.  Nothing runs, so this says nothing about results or
times.  Exit code 1 when any shape fails to compile.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _shapes(cell, truth):
    """(kernel, records, rows) of every kernel call the cell's ops make."""
    import numpy as np

    from bench import selection

    sel = selection.select(truth)
    nf, nr = len(truth.names), truth.ranks
    comm = np.asarray([selection.is_comm(str(n)) for n in truth.names])
    calls, sends = int(sel.name.size), int(sel.m_size.size)
    out = set()
    for op, kw in cell.traffic["ops"]:
        if op == "flat_profile":
            out.add(("seg_sum", calls, nf, len(kw.get("metrics", [1]))))
        elif op == "time_profile":
            out.add(("time_bin", calls, nf, int(kw.get("num_bins", 32))))
        elif op == "load_imbalance":
            out.add(("pair_sum", calls, nf, nr))
        elif op == "stragglers":
            out.add(("seg_sum", int((~comm[sel.name]).sum()), nr, 1))
        elif op == "comm_matrix":
            out.add(("pair_sum", sends, nr, nr))
        elif op == "message_histogram":
            out.add(("hist_bin", sends, int(kw.get("bins", 10)), 0))
    return sorted(s for s in out if s[1] > 0)


def _compile(kind, n, a, b, one_chip):
    import jax
    import jax.numpy as jnp

    from repro.core.accel import block_size
    from repro.kernels.hist_bin import hist_bin
    from repro.kernels.pair_sum import pair_sum
    from repro.kernels.seg_sum import seg_sum
    from repro.kernels.time_bin import time_bin
    i32, f32 = jnp.int32, jnp.float32
    if kind == "seg_sum":
        be = block_size(n, a)
        fn = functools.partial(seg_sum, n_seg=a, be=be, interpret=False)
        args = [((n,), i32), ((b, n), f32)]
    elif kind == "pair_sum":
        be = block_size(n, a + b)
        fn = functools.partial(pair_sum, n_a=a, n_b=b, be=be,
                               interpret=False)
        args = [((n,), i32), ((n,), i32), ((n,), f32)]
    elif kind == "hist_bin":
        be = block_size(n, a)
        fn = functools.partial(hist_bin, n_bins=a, be=be, interpret=False)
        args = [((n,), f32)]
    else:
        be = block_size(n, a + b)
        fn = functools.partial(time_bin, n_funcs=a, n_bins=b, t0=0.0,
                               t1=float(b), be=be, interpret=False)
        args = [((n,), f32), ((n,), f32), ((n,), i32), ((n,), f32)]
    specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    t = time.perf_counter()
    text = jax.jit(fn).lower(*specs).compile().as_text()
    if "tpu_custom_call" not in text:
        raise RuntimeError("compiled without a Mosaic kernel")
    return be, time.perf_counter() - t


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workload", action="append")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    from bench import harness
    from bench.gen import tracegen

    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    failed = 0
    truths = {}
    for w in spec["workloads"]:
        if args.workload and w["name"] not in args.workload:
            continue
        cell = harness.resolve(ROOT, w["name"])
        if w["config"] not in truths:
            truths[w["config"]] = tracegen.generate(cell.config, args.seed,
                                                    None)[1]
        for kind, n, a, b in _shapes(cell, truths[w["config"]]):
            try:
                be, s = _compile(kind, n, a, b, one_chip)
                res = f"block={be} compile_s={s:.2f} ok"
            except Exception as e:  # report every refusal, go on
                failed += 1
                res = f"FAILED {type(e).__name__}: {str(e)[:300]}"
            print(f"{w['name']} {kind} records={n} rows={a}x{b} {res}",
                  flush=True)
    print(f"compile rehearsal: {failed} shape(s) failed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
