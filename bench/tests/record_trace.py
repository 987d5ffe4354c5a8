"""Record the small profiler trace that ``test_bench_xprof.py`` reads.

    python bench/tests/record_trace.py <out_dir>

Run on one TPU chip.  Inside a ``bench:window`` annotation it runs two
analysis kernels through their jit wrappers with known idle gaps between
them, each gap under a named benchmark span, and leaves the
``.xplane.pb`` under ``<out_dir>/plugins/profile/``.  Copy that file to
``bench/tests/data/tiny.xplane.pb``.
"""

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(out_dir: str) -> int:
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import pair_sum_matrix, segment_sum_matrix
    if jax.default_backend() != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 3
    n = 1 << 20
    code = jnp.arange(n, dtype=jnp.int32) % 6
    vals = jnp.ones((2, n), jnp.float32)
    w = jnp.ones((n,), jnp.float32)
    # compile outside the trace
    segment_sum_matrix(code, vals, n_seg=6, be=4096).block_until_ready()
    pair_sum_matrix(code, code, w, n_a=6, n_b=6, be=4096).block_until_ready()
    jax.profiler.start_trace(out_dir)
    ann = jax.profiler.TraceAnnotation
    with ann("bench:window"):
        with ann("bench:kernel:segment_sum_matrix"):
            segment_sum_matrix(code, vals, n_seg=6,
                               be=4096).block_until_ready()
        with ann("bench:canonical_order"):
            time.sleep(0.05)
        with ann("bench:kernel:pair_sum_matrix"):
            pair_sum_matrix(code, code, w, n_a=6, n_b=6,
                            be=4096).block_until_ready()
        with ann("bench:handle_get"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    print(sorted(str(p) for p in Path(out_dir).rglob("*.xplane.pb")))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
