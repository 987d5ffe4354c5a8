"""One run of one benchmark cell, driven by data.

``BENCHMARK.json`` names the cell's configuration and traffic mix; the
harness finds each by name:

* ``bench/configs/<config>.json`` — the deployment (sizes, its ``shape``,
  a module of ``bench/gen``, and its source);
* ``bench/traffic/<traffic>.json`` — the mix: its ``driver`` (a module of
  ``bench/drivers``), its ops, lane, plan cache and streaming settings, and
  the service's workers;
* ``bench/ops/<op>.py`` — the reference and the comparison of each op;
* ``bench/limits/<workload>.json`` — the limit of each number compared;
* ``bench/metrics/<metric>.py`` — one reader per per-layer metric.

A run: generate the trace for the seed (set-up), start the service in this
process, warm up every program the window runs (set-up), measure for
``seconds``, read the device's peak memory, stop the service, then compare
every answer of the window with the plain reference.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

from . import compare, ops, selection
from .gen import tracegen
from .probes import PERTURBATIONS, Probes, kernel_bytes_ops
from .service import Served

BENCH = Path(__file__).resolve().parent


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict[str, float]
    end_to_end: List[Dict]
    per_layer: List[Dict]


def resolve(root: Path, workload: str, spec: Optional[Dict] = None) -> Cell:
    """The cell named ``workload`` in ``spec`` (by default
    ``root/BENCHMARK.json``)."""
    if spec is None:
        spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    limits = compare.limits_for(str(BENCH / "limits" / f"{workload}.json"))
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if workload in m.get("workloads", [workload])
                 and m["moves"] in moved]
    return Cell(workload, int(w["chips"]), config, traffic, limits, e2e,
                per_layer)


def driver(traffic: Dict):
    return importlib.import_module(f"{__package__}.drivers."
                                   f"{traffic['driver']}")


def metric_reader(name: str) -> Callable:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class Context:
    """What a per-layer metric reader may read."""

    window: tuple                   # perf_counter_ns bounds of the window
    requests: int
    probes: Probes
    io: Dict[str, int]              # readers/pack io_stats delta
    stats: tuple = ({}, {})         # the service's /stats at open and close
    profile: Any = None             # bench.xprof.Profile, traced runs only
    peaks: Optional[Dict] = None

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def kernel_calls(self):
        a, b = self.window
        return [(n, s) for n, s, t0, t1 in self.probes.kernel_calls
                if a <= t0 and t1 <= b]

    def roofline_s(self) -> float:
        """The least time the chip could take for the kernel calls of the
        window: per call the larger of bytes over peak bandwidth and
        operations over peak rate."""
        tot = 0.0
        for name, shapes in self.kernel_calls():
            by, ops = kernel_bytes_ops(name, shapes)
            tot += max(by / self.peaks["hbm_bytes_per_s"],
                       ops / self.peaks["flops_per_s"])
        return tot


def _peaks(kind: str) -> Dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def _use_compile_cache(root: Path) -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(root / "bench" / "cache" / "jax"))
    # the analysis kernels compile in well under a second: cache them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, root: Path, *, require_chip: bool = True,
        perturb: Optional[str] = None, data_dir: Optional[str] = None,
        log=print) -> Dict:
    """One run; returns the result line's object (``checks`` last)."""
    import jax
    platform = jax.default_backend()
    if require_chip:
        if platform != "tpu":
            raise NoChip(f"JAX's default backend is {platform!r}, not 'tpu'")
        if len(jax.devices()) < cell.chips:
            raise NoChip(f"{len(jax.devices())} chips, the cell asks for "
                         f"{cell.chips}")
        _use_compile_cache(root)
    dev = jax.devices()[0]
    from repro.readers import pack

    drv = driver(cell.traffic)
    probes = Probes(annotate=trace,
                    perturb=PERTURBATIONS[perturb] if perturb else None)
    probes.install()
    data = Path(data_dir or root / "bench" / "cache" / "trace")
    shutil.rmtree(data, ignore_errors=True)
    served = None
    try:
        t = time.perf_counter()
        paths, truth = tracegen.generate(cell.config, seed, str(data))
        log(f"setup: generated {truth.n_events} events over {truth.ranks} "
            f"ranks in {time.perf_counter() - t:.3f} s", file=sys.stderr)
        plan = drv.plan(cell.traffic, truth, seed, seconds)
        served = Served(paths, cell.traffic["service"], probes).start()
        t = time.perf_counter()
        drv.warm(served, cell.traffic, plan)
        log(f"setup: warm-up {time.perf_counter() - t:.3f} s, "
            f"{len(probes.compiles)} compiles, {len(probes.cache_hits)} "
            f"persistent-cache loads", file=sys.stderr)

        pack.reset_io_stats()
        stats0 = served.stats()
        tracedir = None
        if trace:
            tracedir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(tracedir)
        w0 = time.perf_counter_ns()
        setup_s = time.perf_counter() - t_start
        with probes.span("window"):
            reqs = drv.measure(served, cell.traffic, plan, seconds)
        w1 = time.perf_counter_ns()
        io = pack.io_stats()
        stats1 = served.stats()
        if trace:
            jax.profiler.stop_trace()
        mem = dev.memory_stats() or {}
        mem_peak = int(mem.get("peak_bytes_in_use", 0))
        served.stop()
        served = None

        e2e = drv.end_to_end(reqs, truth)
        e2e["setup_s"] = setup_s
        failed = sum(1 for r in reqs if not r.ok)

        tally = _compare(reqs, truth)
        checks = tally.verdict(cell.limits)
        out: Dict[str, Any] = {
            "correct": compare.passed(checks) and tally.compared > 0,
            "attempted": len(reqs), "failed": failed}
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
        if trace:
            from . import xprof
            ctx = Context(window=(w0, w1), requests=len(reqs), probes=probes,
                          io=io, stats=(stats0, stats1),
                          peaks=_peaks(dev.device_kind) if require_chip
                          else None)
            path = xprof.xplane_path(tracedir)
            if path is not None:
                ctx.profile = xprof.load(path)
            out["metrics"] = _per_layer(cell, ctx)
            if ctx.profile is not None and ctx.profile.busy_s is not None:
                device["busy_s"] = ctx.profile.busy_s
                device["window_s"] = ctx.profile.window_s
                out["breakdown"] = {"device_ops": ctx.profile.device_ops(),
                                    "idle_gaps": ctx.profile.idle_gaps()}
            shutil.rmtree(tracedir, ignore_errors=True)
        else:
            units = {m["name"]: m["unit"] for m in cell.end_to_end}
            out["metrics"] = {k: {"value": v, "unit": units[k]}
                              for k, v in e2e.items() if k in units}
        out["device"] = device
        out["compared"] = tally.compared
        out["checks"] = checks
        return out
    finally:
        if served is not None:
            served.stop()
        probes.remove()
        shutil.rmtree(data, ignore_errors=True)


def _per_layer(cell: Cell, ctx: Context) -> Dict:
    out = {}
    for m in cell.per_layer:
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def _compare(reqs, truth) -> compare.Tally:
    """Every answer of the window against the reference; requests of one
    op with the same arguments share one reference."""
    tally = compare.Tally()
    sel = selection.select(truth)
    cache: Dict[str, Any] = {}
    for r in reqs:
        if not r.ok:
            tally.unanswered()
            continue
        key = r.op + json.dumps(r.kwargs, sort_keys=True)
        if key not in cache:
            cache[key] = ops.reference(r.op, sel, r.kwargs)
        tally.compare(r.op, r.result, cache[key])
    return tally
