"""Distribution-layer tests: logical→physical spec mapping, per-arch rules,
and an 8-virtual-device pjit equivalence check (run in a subprocess so the
forced device count never leaks into other tests)."""

import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import (DEFAULT_RULES, logical_to_spec,
                                        rules_for)

# full-matrix jax suites: minutes, not seconds — slow tier only
pytestmark = pytest.mark.slow


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


M = FakeMesh({"data": 16, "model": 16})
MP = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_logical_to_spec_basics():
    s = logical_to_spec(("embed", "heads"), DEFAULT_RULES, M, (1024, 1024))
    assert s == P("data", "model")
    # missing pod axis silently dropped on single-pod mesh
    s = logical_to_spec(("embed",), DEFAULT_RULES, M, (1024,))
    assert s == P("data")
    s = logical_to_spec(("embed",), DEFAULT_RULES, MP, (1024,))
    assert s == P(("pod", "data"))


def test_divisibility_drops_axis():
    # 60 experts don't divide 16
    s = logical_to_spec(("experts", "embed"), DEFAULT_RULES, M, (60, 2048))
    assert s[0] is None
    # hymba 25-head flat dim divides nothing
    s = logical_to_spec(("heads",), DEFAULT_RULES, M, (25,))
    assert s == P()


def test_no_axis_reuse_across_dims():
    s = logical_to_spec(("embed", "batch"), DEFAULT_RULES, M, (1024, 1024))
    # both want "data" — only the first gets it
    assert s == P("data")


def test_rules_for_archs():
    hymba = rules_for(get_config("hymba-1.5b"), M)
    assert hymba.as_dict()["heads"] is None
    q2 = rules_for(get_config("qwen2-moe-a2.7b"), M)
    assert q2.as_dict()["experts"] is None      # 60 % 16 != 0
    assert q2.as_dict()["expert_mlp"] == "model"
    q3 = rules_for(get_config("qwen3-moe-235b-a22b"), M)
    assert q3.as_dict()["experts"] == "model"   # 128 % 16 == 0 → true EP
    g = rules_for(get_config("gemma3-27b"), M, long_context=True)
    assert g.as_dict()["kv"] == "model"         # 16 KV heads shard
    h = rules_for(get_config("hymba-1.5b"), M, long_context=True)
    assert h.as_dict()["kv_seq"] == "model"     # 5 KV heads → shard seq


SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.distributed.sharding import (activation_sharding, rules_for,
                                            spec_tree)
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = get_smoke_config("qwen1.5-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
    labels = jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": labels}
    loss_1dev = float(model.loss(params, batch))

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = rules_for(cfg, mesh)
    specs = spec_tree(model.param_defs(), rules, mesh)
    pshard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    bshard = {"tokens": NamedSharding(mesh, P("data")),
              "labels": NamedSharding(mesh, P("data"))}

    def loss_fn(p, b):
        with activation_sharding(mesh, rules):
            return model.loss(p, b)
    with mesh:
        f = jax.jit(loss_fn, in_shardings=(pshard, bshard))
        loss_8dev = float(f(params, batch))
    err = abs(loss_8dev - loss_1dev)
    assert err < 1e-4, (loss_1dev, loss_8dev)
    print("SPMD_EQUIV_OK", loss_1dev, loss_8dev)
""")


@pytest.mark.slow
def test_pjit_loss_matches_single_device():
    r = subprocess.run([sys.executable, "-c", SUBPROC], capture_output=True,
                       text=True, cwd=str(__import__("pathlib").Path(
                           __file__).parent.parent))
    assert "SPMD_EQUIV_OK" in r.stdout, r.stdout + r.stderr


SUBPROC_INT8DP = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.compression import pairwise_compressed_mean

    mesh = jax.make_mesh((2,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    g0 = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 0.01
    g1 = jax.random.normal(jax.random.PRNGKey(1), (1000,)) * 0.01
    g = jnp.stack([g0, g1])

    def f(g):
        def per_pod(g):
            out, _ = pairwise_compressed_mean(g[0], "pod", 2)
            return out[None]
        return jax.shard_map(per_pod, mesh=mesh, in_specs=P("pod"),
                             out_specs=P("pod"), check_vma=False)(g)
    with mesh:
        out = jax.jit(f, in_shardings=NamedSharding(mesh, P("pod")))(g)
    want = np.asarray((g0 + g1) / 2)
    got = np.asarray(out[0])
    rel = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert rel < 0.02, rel          # int8 wire quantization error budget
    # the wire format must be int8: look for an s8 ppermute in the HLO
    txt = jax.jit(f, in_shardings=NamedSharding(mesh, P("pod"))).lower(g).compile().as_text()
    assert any("collective-permute" in l and "s8[" in l for l in txt.splitlines())
    print("INT8DP_OK", rel)
""")


@pytest.mark.slow
def test_pairwise_compressed_mean_int8_wire():
    """The cross-pod gradient mean uses an int8 wire format (ppermute of s8)
    and stays within the quantization error budget."""
    r = subprocess.run([sys.executable, "-c", SUBPROC_INT8DP],
                       capture_output=True, text=True,
                       cwd=str(__import__("pathlib").Path(
                           __file__).parent.parent))
    assert "INT8DP_OK" in r.stdout, r.stdout + r.stderr
