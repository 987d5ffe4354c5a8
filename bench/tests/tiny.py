"""Tiny sizes of the benchmark's cells, for tests on the CPU."""

import copy
import json

from bench import harness

TINY = {
    "npbmz": dict(ranks=8, x_zones=4, y_zones=2, niter=6, chunk_rows=128),
}


def spec():
    return json.loads((harness.BENCH.parent / "BENCHMARK.json").read_text())


CELLS = [w["name"] for w in spec()["workloads"]]
CONFIGS = [c["name"] for c in spec()["configs"]]


def config(cfg):
    out = copy.deepcopy(cfg)
    out.update(TINY[cfg["shape"]])
    return out


def config_named(name):
    return config(json.loads((harness.BENCH / "configs" / f"{name}.json")
                             .read_text()))


def cell(workload):
    c = harness.resolve(harness.BENCH.parent, workload, spec())
    c.config = config(c.config)
    return c
