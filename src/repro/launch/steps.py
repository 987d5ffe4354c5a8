"""Cell builder: for an (architecture × shape × mesh) cell, produce the step
function, its abstract inputs (ShapeDtypeStructs), and in/out shardings —
everything ``dryrun.py`` needs to ``.lower().compile()`` and everything
``train.py``/``serve.py`` need to run for real.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..distributed.sharding import (activation_sharding, batch_spec,
                                    logical_to_spec, rules_for, spec_tree)
from ..models import build_model, input_specs
from ..models.config import ModelConfig, ShapeConfig
from ..models.layers import abstract_tree
from ..optim import adamw_update, cosine_schedule
from ..optim.adamw import AdamWState, abstract_adamw_state

__all__ = ["Cell", "build_cell"]


@dataclasses.dataclass
class Cell:
    name: str
    fn: Callable
    abstract_args: Tuple[Any, ...]
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    donate_argnums: Tuple[int, ...]
    meta: Dict[str, Any]

    def lower(self, mesh: Mesh):
        with mesh:
            jitted = jax.jit(self.fn, in_shardings=self.in_shardings,
                             out_shardings=self.out_shardings,
                             donate_argnums=self.donate_argnums)
            return jitted.lower(*self.abstract_args)


def _named(mesh: Mesh, tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


def _batch_shardings(mesh: Mesh, specs: Dict[str, jax.ShapeDtypeStruct]):
    out = {}
    for k, v in specs.items():
        if v.ndim == 0:
            out[k] = NamedSharding(mesh, P())
        else:
            bs = batch_spec(mesh, v.shape[0])
            pad = v.ndim - 1
            parts = list(bs) + [None] * pad
            out[k] = NamedSharding(mesh, P(*parts))
    return out


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
               dtype=jnp.bfloat16, rules=None,
               lr_schedule: Optional[Callable] = None) -> Cell:
    msize = mesh.shape.get("model", 1)
    if (cfg.n_heads % msize == 0 and cfg.n_kv_heads % msize
            and (cfg.n_heads // cfg.n_kv_heads) % msize):
        cfg = dataclasses.replace(cfg, attn_broadcast_kv=True)
    if cfg.n_experts and shape.kind != "decode":
        # grouped MoE dispatch aligned with the data shards (§Perf iter. 2)
        dsize = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
        T = shape.global_batch * shape.seq_len
        if T % dsize == 0:
            cfg = dataclasses.replace(cfg, moe_groups=dsize)
    model = build_model(cfg)
    rules = rules or rules_for(cfg, mesh,
                               long_context=shape.name == "long_500k")
    pdefs = model.param_defs()
    pspecs = spec_tree(pdefs, rules, mesh)
    pshard = _named(mesh, pspecs)
    aparams = abstract_tree(pdefs, dtype)
    inputs = input_specs(cfg, shape, dtype)
    meta = {"arch": cfg.name, "shape": shape.name, "rules": rules.as_dict(),
            "params": cfg.param_count(),
            "active_params": cfg.active_param_count()}

    if shape.kind == "train":
        aopt = abstract_adamw_state(aparams)
        oshard = AdamWState(step=NamedSharding(mesh, P()),
                            m=_named(mesh, pspecs), v=_named(mesh, pspecs))
        bshard = _batch_shardings(mesh, inputs)

        def train_step(params, opt_state, batch):
            with activation_sharding(mesh, rules):
                loss, grads = jax.value_and_grad(model.loss)(params, batch)
            lr = (lr_schedule or (lambda s: cosine_schedule(s, 3e-4, 2000,
                                                            100_000)))(
                opt_state.step)
            params, opt_state = adamw_update(params, grads, opt_state, lr)
            return params, opt_state, loss

        return Cell(
            name=f"{cfg.name}:{shape.name}", fn=train_step,
            abstract_args=(aparams, aopt, inputs),
            in_shardings=(pshard, oshard, bshard),
            out_shardings=(pshard, oshard, NamedSharding(mesh, P())),
            donate_argnums=(0, 1), meta=meta)

    if shape.kind == "prefill":
        bshard = _batch_shardings(mesh, inputs)
        cdefs = model.cache_defs(shape.global_batch, shape.seq_len)
        cspecs = spec_tree(cdefs, rules, mesh)

        def prefill(params, batch):
            kw = {k: v for k, v in batch.items() if k != "tokens"}
            with activation_sharding(mesh, rules):
                cache, logits, _ = model.prefill(params, batch["tokens"],
                                                 shape.seq_len, **kw)
            return cache, logits

        return Cell(
            name=f"{cfg.name}:{shape.name}", fn=prefill,
            abstract_args=(aparams, inputs),
            in_shardings=(pshard, bshard),
            out_shardings=(_named(mesh, cspecs),
                           NamedSharding(mesh, batch_spec(
                               mesh, shape.global_batch))),
            donate_argnums=(), meta=meta)

    # decode: one new token against a cache of seq_len entries
    B, S = shape.global_batch, shape.seq_len
    acache = model.init_cache(B, S, dtype, abstract=True)
    cdefs = model.cache_defs(B, S)
    cshard = _named(mesh, spec_tree(cdefs, rules, mesh))
    bshard = _batch_shardings(mesh, inputs)

    def serve_step(params, cache, batch):
        with activation_sharding(mesh, rules):
            logits, cache = model.decode_step(params, cache, batch["token"],
                                              batch["pos"], S)
        return logits, cache

    return Cell(
        name=f"{cfg.name}:{shape.name}", fn=serve_step,
        abstract_args=(aparams, acache, inputs),
        in_shardings=(pshard, cshard, bshard),
        out_shardings=(NamedSharding(mesh, batch_spec(mesh, B)), cshard),
        donate_argnums=(1,), meta=meta)


def build_compressed_dp_cell(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                             dtype=jnp.bfloat16,
                             lr_schedule: Optional[Callable] = None) -> Cell:
    """Cross-pod data parallelism with an **int8 gradient wire format**.

    Layout: FSDP×TP *within* a pod; params/optimizer replicated *across*
    pods; each pod computes gradients for its batch shard and the cross-pod
    mean runs over a ppermute'd int8 payload
    (`distributed.compression.pairwise_compressed_mean`) — 2× less inter-pod
    (DCN) traffic than a bf16 all-reduce at 2 pods.  Built with a
    partial-auto shard_map: only ``pod`` is manual; ``data``/``model`` stay
    GSPMD-auto so every activation constraint applies unchanged.

    STATUS: experimental.  The collective itself is validated end-to-end
    (tests/test_distributed.py::test_pairwise_compressed_mean_int8_wire:
    s8 collective-permute on the wire, <2% quantization error, exact with
    error feedback).  Lowering the *full model* under partial-manual
    shard_map currently trips an XLA SPMD-partitioner CHECK
    (spmd_partitioner_util.cc:504, gather partitioning inside a
    partial-manual region; jax 0.8.2) — upstream bug, reproducer kept in
    EXPERIMENTS.md §Perf; the production path remains FSDP-over-(pod,data).
    """
    from ..distributed.compression import pairwise_compressed_mean

    assert "pod" in mesh.shape and shape.kind == "train"
    n_pods = mesh.shape["pod"]
    msize = mesh.shape.get("model", 1)
    if (cfg.n_heads % msize == 0 and cfg.n_kv_heads % msize
            and (cfg.n_heads // cfg.n_kv_heads) % msize):
        cfg = dataclasses.replace(cfg, attn_broadcast_kv=True)
    if cfg.n_experts:
        dsize = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
        T = shape.global_batch * shape.seq_len
        if T % dsize == 0:
            cfg = dataclasses.replace(cfg, moe_groups=dsize)
    # params replicated across pod → FSDP over data only.  vocab/embedding
    # stays replicated along `model`: a vocab-sharded gather inside the
    # partial-manual region trips an XLA SPMD-partitioner CHECK
    # (spmd_partitioner_util.cc:504, jax 0.8.2) — documented workaround.
    rules = rules_for(cfg, mesh).override(embed=("data",),
                                          batch=("pod", "data"),
                                          vocab=None, act_vocab=None)
    model = build_model(cfg)
    pdefs = model.param_defs()
    pspecs = spec_tree(pdefs, rules, mesh)
    pshard = _named(mesh, pspecs)
    aparams = abstract_tree(pdefs, dtype)
    aopt = abstract_adamw_state(aparams)
    oshard = AdamWState(step=NamedSharding(mesh, P()),
                        m=_named(mesh, pspecs), v=_named(mesh, pspecs))
    inputs = input_specs(cfg, shape, dtype)
    bshard = _batch_shardings(mesh, inputs)

    def train_step(params, opt_state, batch):
        def per_pod(params, opt_state, batch):
            with activation_sharding(mesh, rules,
                                     manual_axes=frozenset({"pod"})):
                loss, grads = jax.value_and_grad(model.loss)(params, batch)
            flat, tree = jax.tree_util.tree_flatten(grads)
            red = [pairwise_compressed_mean(g, "pod", n_pods)[0]
                   for g in flat]
            grads = jax.tree_util.tree_unflatten(tree, red)
            lr = (lr_schedule or (lambda s: cosine_schedule(
                s, 3e-4, 2000, 100_000)))(opt_state.step)
            params, opt_state = adamw_update(params, grads, opt_state, lr)
            return params, opt_state, jax.lax.pmean(loss, "pod")

        in_specs = (jax.tree_util.tree_map(lambda s: P(), params),
                    jax.tree_util.tree_map(lambda s: P(), opt_state,
                                           is_leaf=lambda x: hasattr(x, "shape")),
                    {k: (P("pod") if getattr(v, "ndim", 0) else P())
                     for k, v in batch.items()})
        out_specs = in_specs[:2] + (P(),)
        # replication checking off: the int8-wire collective is
        # deliberately non-replicated
        return jax.shard_map(per_pod, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False,
                             axis_names={"pod"})(params, opt_state, batch)

    return Cell(
        name=f"{cfg.name}:{shape.name}:int8dp", fn=train_step,
        abstract_args=(aparams, aopt, inputs),
        in_shardings=(pshard, oshard, bshard),
        out_shardings=(pshard, oshard, NamedSharding(mesh, P())),
        donate_argnums=(0, 1),
        meta={"arch": cfg.name, "shape": shape.name,
              "rules": rules.as_dict(), "params": cfg.param_count(),
              "active_params": cfg.active_param_count(),
              "grad_wire": "int8+error-feedback"})
