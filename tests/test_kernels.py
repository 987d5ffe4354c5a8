"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import (flash_attention_gqa, router_topk,
                               time_profile_matrix)
from repro.kernels.pair_sum import pair_sum, tile_shape
from repro.models.attention import chunked_attention

# full-matrix jax suites: minutes, not seconds — slow tier only
pytestmark = pytest.mark.slow


@pytest.mark.parametrize("B,S,H,KVH,D", [
    (1, 64, 2, 1, 32), (2, 128, 4, 2, 64), (1, 192, 4, 4, 128),
    (1, 256, 8, 2, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(B, S, H, KVH, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KVH, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KVH, D), dtype)
    out = flash_attention_gqa(q, k, v, bq=64, bk=64)
    want = chunked_attention(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("window,prefix", [(16, 0), (32, 8), (None, 0)])
def test_flash_attention_masks(window, prefix):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 160, 2, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 160, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 160, 2, 32), jnp.float32)
    out = flash_attention_gqa(q, k, v, window=window, prefix_len=prefix,
                              bq=64, bk=32)
    want = chunked_attention(q, k, v, window=window, prefix_len=prefix)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 96, 2, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 96, 1, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 96, 1, 32), jnp.float32)
    out = flash_attention_gqa(q, k, v, causal=False, bq=32, bk=32)
    want = chunked_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("N,F,NB", [(100, 7, 16), (1000, 13, 64), (53, 3, 8)])
def test_time_bin_kernel(N, F, NB):
    key = jax.random.PRNGKey(0)
    s = jax.random.uniform(key, (N,)) * 100
    e = s + jax.random.uniform(jax.random.PRNGKey(1), (N,)) * 10
    f = jax.random.randint(jax.random.PRNGKey(2), (N,), 0, F)
    out = time_profile_matrix(s, e, f, n_funcs=F, n_bins=NB, t0=0.0, t1=110.0)
    want = ref.time_bin_ref(s, e, f, n_funcs=F, n_bins=NB, t0=0.0, t1=110.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-3)
    # conservation: total binned time == total clipped durations
    assert float(np.asarray(out).sum()) == pytest.approx(
        float(np.asarray(want).sum()))


def _records(rng, n, n_a, n_b, integer):
    """Seeded records over a [n_a, n_b] output, a few ids out of range
    (ignored); rows from 2/3 of n_a on get none, so some tiles are empty."""
    a = rng.integers(-1, 2 * n_a // 3, n).astype(np.int32)
    b = rng.integers(-1, n_b + 2, n).astype(np.int32)
    w = (rng.integers(1, 1 << 12, n) if integer
         else rng.standard_normal(n) * 1e3).astype(np.float32)
    return a, b, w


def _numpy_pair_sum(a, b, w, n_a, n_b):
    ok = (a >= 0) & (a < n_a) & (b >= 0) & (b < n_b)
    out = np.zeros((n_a, n_b))
    np.add.at(out, (a[ok], b[ok]), w[ok].astype(np.float64))
    return out


# (n_a, n_b, tile, records, block): 300x200 cut into uneven tiles, the
# whole output as one tile, and a tiled output with no or few records
@pytest.mark.parametrize("n_a,n_b,tile,n,be", [
    (300, 200, (64, 128), 1500, 128), (300, 200, (128, 128), 700, 256),
    (300, 200, None, 1500, 256), (300, 200, (64, 128), 0, 128),
    (300, 200, (64, 128), 3, 128)])
@pytest.mark.parametrize("integer", [True, False], ids=["int", "float"])
def test_pair_sum_tiled_kernel(n_a, n_b, tile, n, be, integer):
    rng = np.random.default_rng([n_a, n_b, n, be])
    a, b, w = _records(rng, n, n_a, n_b, integer)
    out = np.asarray(jax.jit(lambda a, b, w: pair_sum(
        a, b, w, n_a=n_a, n_b=n_b, be=be, tile=tile))(a, b, w))
    assert out.shape == (n_a, n_b)
    want = _numpy_pair_sum(a, b, w, n_a, n_b)
    if integer:
        # integer sums below 2^24 are exact in f32, in any order
        np.testing.assert_array_equal(out, want)
        np.testing.assert_array_equal(out, np.asarray(ref.pair_sum_ref(
            a, b, w, n_a=n_a, n_b=n_b)))
    else:
        # one-hot products are exact at HIGHEST; a cell sums a few records
        # in f32, so it is within a few f32 roundings of its magnitude
        np.testing.assert_allclose(out, want, rtol=0,
                                   atol=4e-7 * np.abs(want).max())


def test_pair_sum_tile_shape():
    assert tile_shape(256, 256) == (256, 256)        # one tile, as before
    assert tile_shape(18, 4096) == (18, 4096)
    assert tile_shape(4096, 4096) == (512, 512)
    assert tile_shape(18, 100_000) == (18, 512)      # the narrow side whole


@pytest.mark.parametrize("T,E,k", [(64, 8, 2), (777, 64, 4), (32, 128, 8)])
def test_topk_gating_kernel(T, E, k):
    lg = jax.random.normal(jax.random.PRNGKey(0), (T, E), jnp.float32)
    idx, g = router_topk(lg, k)
    ri, rg = ref.topk_gating_ref(lg, k)
    assert (np.asarray(idx) == np.asarray(ri)).all()
    np.testing.assert_allclose(np.asarray(g), np.asarray(rg), atol=1e-6)
    np.testing.assert_allclose(np.asarray(g).sum(-1), 1.0, atol=1e-5)


def test_time_profile_pallas_backend_matches_numpy():
    """Trace.time_profile(backend='pallas') routes through the Pallas kernel
    and must equal the exact NumPy sweep."""
    from repro import tracegen as tg
    t = tg.tortuga(nprocs=4, iters=2)
    a = t.time_profile(num_bins=16)
    b = t.time_profile(num_bins=16, backend="pallas")
    cols = [c for c in a.columns if c not in ("bin_start", "bin_end")]
    assert cols == [c for c in b.columns if c not in ("bin_start", "bin_end")]
    for c in cols:
        np.testing.assert_allclose(np.asarray(b[c]), np.asarray(a[c]),
                                   rtol=1e-5, atol=1e-3)
