"""Logical-axis → mesh-axis sharding rules + activation constraints
(MaxText-style).

Every parameter/cache leaf is declared with *logical* axes (see
``repro.models.layers.ParamDef``); a ``ShardingRules`` table maps logical axis
names to physical mesh axes.  The production mesh axes are

* ``pod``   — inter-pod data parallelism (multi-pod mesh only),
* ``data``  — intra-pod data parallel / FSDP axis,
* ``model`` — tensor/expert/sequence parallel axis.

The defaults implement FSDP(embed) × TP(heads/mlp/vocab) × EP(experts); archs
whose dimensions don't divide the axis (hymba's 25 heads, qwen2-moe's 60
experts) override single rules instead of forking the model code.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ShardingRules", "DEFAULT_RULES", "rules_for", "logical_to_spec",
           "spec_tree", "batch_spec", "named_sharding_tree",
           "activation_sharding", "constrain"]


Axis = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    rules: Tuple[Tuple[str, Axis], ...]

    def as_dict(self) -> Dict[str, Axis]:
        return dict(self.rules)

    def override(self, **kw: Axis) -> "ShardingRules":
        d = self.as_dict()
        d.update(kw)
        return ShardingRules(tuple(d.items()))


# fsdp axes: both pod and data shard the embed dim of weights (ZeRO-3 style);
# on the single-pod mesh "pod" is absent and is dropped automatically.
_FSDP = ("pod", "data")

DEFAULT_RULES = ShardingRules((
    ("batch", _FSDP),          # activations' batch dim
    ("seq", None),
    ("embed", _FSDP),          # weights' d_model dim → FSDP
    ("embed2", None),
    ("vocab", "model"),
    ("heads", "model"),
    ("kv", None),              # few KV heads — replicate (GQA); per-arch
    ("mlp", "model"),
    ("expert_mlp", "model"),
    ("experts", "model"),      # EP
    ("ssm_in", "model"),
    ("layers", None),
    ("layers_inner", None),
    ("kv_seq", None),          # decode-cache sequence dim (long_500k: model)
    # --- activation logical axes (with_sharding_constraint targets) -------
    ("act_batch", _FSDP),
    ("act_seq", None),
    ("act_embed", None),
    ("act_heads", "model"),
    ("act_kv", None),          # per-arch: "model" when KVH divides
    ("act_kv_group", None),    # GQA carry [B,KVH,G,...]: shard KVH…
    ("act_q_group", "model"),  # …or the per-KV query group G
    ("act_ff", "model"),
    ("act_exp", "model"),
    ("act_ssm_heads", "model"),
    ("act_vocab", "model"),
))


def rules_for(cfg, mesh: Mesh, *, long_context: bool = False
              ) -> ShardingRules:
    """Per-arch rule adjustments for divisibility + shape kind."""
    r = DEFAULT_RULES
    msize = mesh.shape.get("model", 1)
    dsize = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    if cfg.n_heads % msize:
        r = r.override(heads=None, act_heads=None)       # hymba: 25 heads
    if cfg.n_kv_heads % msize == 0:
        # enough KV heads to shard them (MHA/kv-rich GQA: qwen05, whisper,
        # codeqwen, phi3, gemma3, qwen2-moe)
        r = r.override(kv="model", act_kv="model", act_kv_group="model",
                       act_q_group=None)
    elif cfg.n_heads % msize == 0 and (cfg.n_heads // cfg.n_kv_heads) % msize:
        # neither KVH nor G divides, but H does (qwen1.5-110b 64H kv8):
        # KV is broadcast to H heads (cfg.attn_broadcast_kv) and the merged
        # head dim shards; divisibility checks guard the non-broadcast paths
        r = r.override(act_kv="model", act_kv_group="model",
                       act_q_group=None)
    if cfg.n_experts and cfg.n_experts % msize:
        r = r.override(experts=None, expert_mlp="model")  # qwen2-moe: 60 experts
    if cfg.d_model % dsize:
        r = r.override(embed=None, batch="data", act_batch="data")
    if cfg.family in ("ssm", "hybrid"):
        d_in = cfg.ssm_expand * cfg.d_model
        if d_in % msize:
            r = r.override(ssm_in=None)
        if (d_in // cfg.ssm_headdim) % msize:
            r = r.override(act_ssm_heads=None)
    if long_context:
        # batch=1: the 500k KV cache must shard on `model`.  Prefer sharding
        # KV heads (keeps attention local per head); fall back to the cache
        # sequence dim when heads don't divide.
        if cfg.n_kv_heads % msize == 0:
            r = r.override(kv="model")
        else:
            r = r.override(kv_seq="model")
    return r


def logical_to_spec(axes: Tuple[Optional[str], ...], rules: ShardingRules,
                    mesh: Mesh, shape: Optional[Tuple[int, ...]] = None) -> P:
    """Map one leaf's logical axes to a PartitionSpec, dropping mesh axes that
    are absent or that don't divide the dimension."""
    table = rules.as_dict()
    used = set()
    out = []
    for i, ax in enumerate(axes):
        phys = table.get(ax) if ax else None
        if phys is None:
            out.append(None)
            continue
        cand = (phys,) if isinstance(phys, str) else tuple(phys)
        cand = tuple(a for a in cand if a in mesh.shape and a not in used)
        if shape is not None and cand:
            n = 1
            kept = []
            for a in cand:
                if shape[i] % (n * mesh.shape[a]) == 0:
                    kept.append(a)
                    n *= mesh.shape[a]
            cand = tuple(kept)
        if not cand:
            out.append(None)
        else:
            used.update(cand)
            out.append(cand[0] if len(cand) == 1 else cand)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def spec_tree(defs, rules: ShardingRules, mesh: Mesh):
    """ParamDef tree → PartitionSpec tree (divisibility-checked)."""
    from ..models.layers import map_defs
    return map_defs(lambda d: logical_to_spec(d.axes, rules, mesh, d.shape),
                    defs)


def named_sharding_tree(defs, rules: ShardingRules, mesh: Mesh):
    from ..models.layers import map_defs
    return map_defs(
        lambda d: NamedSharding(mesh, logical_to_spec(d.axes, rules, mesh,
                                                      d.shape)), defs)


# ---------------------------------------------------------------------------
# activation sharding constraints (trace-time ambient context)
# ---------------------------------------------------------------------------
# GSPMD propagates input/param shardings, but long scan/while bodies lose
# them (the carried tuple gets one inferred sharding — measured: the
# attention online-softmax carry replicated the *global batch* per device,
# a 12× per-device FLOP blowup).  Model code calls ``constrain(x, axes…)``
# at key points; inside an ``activation_sharding(mesh, rules)`` context this
# becomes ``with_sharding_constraint``; otherwise it is a no-op, so tests
# and single-device runs are untouched.

import contextlib

_ACT_CTX: list = []


@contextlib.contextmanager
def activation_sharding(mesh: Mesh, rules: ShardingRules,
                        manual_axes: frozenset = frozenset()):
    """``manual_axes``: mesh axes that are *manual* in an enclosing
    shard_map (e.g. {"pod"} in the compressed-DP step) — they are stripped
    from constraint specs, and the constraint binds as a bare PartitionSpec
    against the context's abstract mesh."""
    _ACT_CTX.append((mesh, rules, manual_axes))
    try:
        yield
    finally:
        _ACT_CTX.pop()


def constrain(x, *axes):
    """Apply a logical-axis sharding constraint (no-op outside context)."""
    if not _ACT_CTX:
        return x
    mesh, rules, manual = _ACT_CTX[-1]
    spec = logical_to_spec(tuple(axes), rules, mesh, tuple(x.shape))
    if manual:
        parts = []
        for prt in spec:
            if prt is None:
                parts.append(None)
            elif isinstance(prt, tuple):
                kept = tuple(a for a in prt if a not in manual)
                parts.append(kept if len(kept) > 1 else
                             (kept[0] if kept else None))
            else:
                parts.append(None if prt in manual else prt)
        return jax.lax.with_sharding_constraint(x, P(*parts))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def batch_spec(mesh: Mesh, batch: int) -> P:
    """Sharding for [B, ...] host inputs: batch over (pod, data) if divisible."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n = 1
    kept = []
    for a in axes:
        if batch % (n * mesh.shape[a]) == 0:
            kept.append(a)
            n *= mesh.shape[a]
    if not kept:
        return P()
    return P(tuple(kept) if len(kept) > 1 else kept[0])
