"""Run one cell of the on-chip benchmark and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1

from the root of a checkout that holds ``BENCHMARK.json``, ``bench/`` and
the program under ``src/``.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number the correctness comparison read, beside its limit.
The same numbers end standard error.

The run exits with code 3, and prints no result, when JAX finds no TPU or
fewer chips than the cell asks for.

``--perturb control-bf16|fault-alter|fault-half`` runs the lower-precision
control or a planted fault instead of the program's own kernels; the
benchmark's own runs never pass it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--perturb", default=None,
                    choices=("control-bf16", "fault-alter", "fault-half"))
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness

    cell = harness.resolve(ROOT, args.workload)
    try:
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          T_START, ROOT, perturb=args.perturb)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
