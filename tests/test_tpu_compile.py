"""Compile rehearsals of the four analysis kernels for a TPU v5e.

Each test compiles one kernel (``interpret=False``) for a v5e that is
described, not attached, at the shapes of a 10M-event trace: ~4.4M call
records or messages, 6 or 1024 names, 256 or 4096 ranks (a 4096 x 4096
``pair_sum`` output is 64 MiB, tiled past VMEM), 32 time bins, with the
block size :func:`repro.core.accel.block_size` picks.  What the TPU
compiler refuses here (layouts it cannot lower, tiles that overflow VMEM,
programs too big for the chip) it would refuse on the chip.  Nothing runs,
so these say nothing about results or times.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.accel import block_size
from repro.kernels.hist_bin import hist_bin
from repro.kernels.pair_sum import pair_sum, tile_shape
from repro.kernels.seg_sum import seg_sum
from repro.kernels.time_bin import time_bin

N_CALLS = 4_400_000
CHIP_BYTES = 16 << 30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _seg(S):
    be = block_size(N_CALLS, S)
    return (functools.partial(seg_sum, n_seg=S, be=be, interpret=False),
            [((N_CALLS,), jnp.int32), ((2, N_CALLS), jnp.float32)])


def _pair(A, B):
    ta, tb = tile_shape(A, B)
    be = block_size(N_CALLS, ta + tb)
    return (functools.partial(pair_sum, n_a=A, n_b=B, be=be,
                              interpret=False),
            [((N_CALLS,), jnp.int32)] * 2 + [((N_CALLS,), jnp.float32)])


def _hist(NB):
    be = block_size(N_CALLS, NB)
    return (functools.partial(hist_bin, n_bins=NB, be=be, interpret=False),
            [((N_CALLS,), jnp.float32)])


def _time(F, NB):
    be = block_size(N_CALLS, F + NB)
    return (functools.partial(time_bin, n_funcs=F, n_bins=NB, t0=0.0,
                              t1=float(NB), be=be, interpret=False),
            [((N_CALLS,), jnp.float32)] * 2 + [((N_CALLS,), jnp.int32)]
            + [((N_CALLS,), jnp.float32)])


@pytest.mark.parametrize("case", [
    ("seg_sum", 6), ("seg_sum", 1024), ("pair_sum", 256, 256),
    ("pair_sum", 4096, 4096),
    ("hist_bin", 32), ("time_bin", 6, 32), ("time_bin", 1024, 32),
], ids=lambda c: "-".join(map(str, c)))
def test_kernel_compiles_for_v5e(one_chip, case):
    build = {"seg_sum": _seg, "pair_sum": _pair, "hist_bin": _hist,
             "time_bin": _time}[case[0]]
    fn, shapes = build(*case[1:])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < CHIP_BYTES, used


def test_pair_sum_maps_fit_smem_at_any_record_count(one_chip):
    """``pair_sum``'s scalar-prefetched maps are per output tile, not per
    grid step: 200M records (195,313 record blocks) still compile, where
    maps of a few words a step would overflow the v5e's 1 MiB of SMEM."""
    n = 200_000_000
    fn = functools.partial(pair_sum, n_a=18, n_b=256,
                           be=block_size(n, 18 + 256), interpret=False)
    args = [jax.ShapeDtypeStruct((n,), d, sharding=one_chip)
            for d in (jnp.int32, jnp.int32, jnp.float32)]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
