"""Step functions + sharding trees for training and serving.

Builders return (step_fn, input ShapeDtypeStructs, in/out shardings) so the
same artifacts drive real execution (examples, smoke tests) and the
``.lower().compile()`` dry-run on the 512-device mesh.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeSpec
from repro.data.pipeline import batch_logical_axes, batch_specs
from repro.distributed import sharding as shd
from repro.models import LM
from repro.optim.adamw import (AdamWConfig, adamw_init, adamw_state_axes,
                               adamw_state_shapes, adamw_update)

Tree = Any


def rules_for(cfg: ModelConfig, *, params: bool = False) -> Dict[str, Any]:
    """Sharding rules for this config (activation rules by default; the
    param-only FSDP overlay with params=True).

    Profiles (hillclimb levers; see EXPERIMENTS.md §Perf):
      "tp"  — Megatron TP over "model" (the baseline rules)
      "dp"  — pure data parallelism: the model axis joins the batch axes and
              all weights replicate.  Right for models whose layers are too
              small to amortize TP collectives (whisper-small & co.)
    """
    rules = dict(shd.BASE_RULES)
    if cfg.sharding_profile == "dp":
        rules.update(
            batch=("pod", "data", "model"),
            cache_batch=("pod", "data", "model"),
            vocab=None, qkv=None, heads=None, mlp=None,
            ssm_inner=None, ssm_heads=None,
            embed_shard=None, cache_hd=None,
            expert="model" if cfg.num_experts else None,
        )
    elif cfg.sharding_profile == "zero3cp":
        # context parallelism + output-dim ZeRO-3: activations shard
        # (batch, seq) and never the feature dims, so feature matmuls need
        # NO tensor-parallel reduction.  Weights are STORED sharded over
        # (data x model) on their OUTPUT dim (the "__reverse__" resolution)
        # and all-gathered at use — ~2.8 GB/layer of AG replaces
        # ~13 GB/matmul of partial-sum all-reduce.
        rules.update(
            batch=("pod", "data"), seq="model",
            vocab=None, qkv=None, heads=None, mlp=None,
            ssm_inner=None, ssm_heads=None, embed_shard=None,
            expert="model" if cfg.num_experts else None,
            __gather_weights__=True,       # explicit AG-at-use (layers.GW)
        )
        if params:
            two_d = ("data", "model")
            rules.update(qkv=two_d, mlp=two_d, embed=two_d, vocab=two_d,
                         vocab_rep=None, embed_shard=two_d,
                         ssm_inner=two_d, ssm_heads=two_d, lora=two_d,
                         __reverse__=True, __gather_weights__=False)
    if cfg.sequence_parallel:
        # residual/norm activations shard their seq axis over "model";
        # XLA gathers seq only around attention (Megatron-SP pattern)
        rules["seq"] = "model"
    if cfg.use_flash:
        # flash_decode reads whole heads at every position: the cache
        # shards its heads over the model axis, never head dim or length
        rules.update(cache_heads="model", cache_hd=None, cache_seq=None)
    elif cfg.decode_cache_shard == "seq":
        rules.update(cache_seq="model", cache_hd=None)
    if params and cfg.fsdp and cfg.sharding_profile == "tp":
        # ZeRO-3 overlay: weights' embed-ish axes also shard over data
        rules.update(embed="data", vocab_rep="data", mlp_fsdp="data")
    return rules


def make_optimizer_config(cfg: ModelConfig, total_steps: int = 10_000
                          ) -> AdamWConfig:
    from repro.optim import make_optimizer
    return make_optimizer(cfg.optimizer, total_steps=total_steps,
                          grad_compress=cfg.grad_compress)


# ---------------------------------------------------------------------------
# training


def make_train_step(model: LM, opt_cfg: AdamWConfig,
                    grad_specs: Optional[Tree] = None):
    """grad_specs: optional tree of PartitionSpec matching the params —
    constraining grads to the PARAM sharding right at the autodiff boundary
    lets GSPMD lower the gradient sync as reduce-scatter instead of
    all-reduce (half the wire bytes) since nothing downstream ever needs the
    unsharded gradient."""
    def train_step(state: Tree, batch: Dict[str, jax.Array]
                   ) -> Tuple[Tree, jax.Array]:
        def loss_fn(p):
            return model.loss(p, batch)
        loss, grads = jax.value_and_grad(loss_fn)(state["params"])
        if grad_specs is not None:
            grads = jax.tree.map(jax.lax.with_sharding_constraint,
                                 grads, grad_specs)
        params2, opt2 = adamw_update(state["params"], grads, state["opt"],
                                     opt_cfg)
        return {"params": params2, "opt": opt2}, loss
    return train_step


def train_state_shapes(model: LM, opt_cfg: AdamWConfig) -> Tree:
    ps = model.shapes()
    return {"params": ps, "opt": adamw_state_shapes(ps, opt_cfg)}


def train_state_axes(model: LM, opt_cfg: AdamWConfig) -> Tree:
    ax = model.logical_axes()
    return {"params": ax, "opt": adamw_state_axes(ax, opt_cfg)}


def init_train_state(model: LM, opt_cfg: AdamWConfig, rng: jax.Array) -> Tree:
    params = model.init(rng)
    return {"params": params, "opt": adamw_init(params, opt_cfg)}


def train_shardings(model: LM, opt_cfg: AdamWConfig, mesh: Mesh,
                    shape: ShapeSpec) -> Tuple[Tree, Tree]:
    """(state shardings, batch shardings) for this mesh."""
    cfg = model.cfg
    st_ax = train_state_axes(model, opt_cfg)
    st_sh = train_state_shapes(model, opt_cfg)
    prules = rules_for(cfg, params=True)
    st_specs = shd.specs_for_tree(st_ax, st_sh, rules=prules)
    b_ax = batch_logical_axes(cfg, shape)
    b_sh = batch_specs(cfg, shape)
    b_specs = shd.specs_for_tree(b_ax, b_sh, rules=rules_for(cfg))
    return (shd.named_shardings(mesh, st_specs),
            shd.named_shardings(mesh, b_specs))


# ---------------------------------------------------------------------------
# serving


def make_prefill_step(model: LM):
    def prefill_step(params: Tree, batch: Dict[str, jax.Array], cache: Tree
                     ) -> Tuple[jax.Array, Tree]:
        return model.prefill(params, batch, cache)
    return prefill_step


def make_decode_step(model: LM):
    def decode_step(params: Tree, batch: Dict[str, jax.Array], cache: Tree,
                    pos: jax.Array) -> Tuple[jax.Array, Tree]:
        return model.decode_step(params, batch, cache, pos)
    return decode_step


def serve_shardings(model: LM, mesh: Mesh, shape: ShapeSpec
                    ) -> Tuple[Tree, Tree, Tree]:
    """(param, batch, cache) shardings for a serve cell."""
    cfg = model.cfg
    rules = rules_for(cfg)
    prules = rules_for(cfg, params=True)
    p_specs = shd.specs_for_tree(model.logical_axes(), model.shapes(),
                                 rules=prules)
    b_ax = batch_logical_axes(cfg, shape)
    b_sh = batch_specs(cfg, shape)
    b_specs = shd.specs_for_tree(b_ax, b_sh, rules=rules)
    c_ax = model.cache_logical_axes(shape.global_batch, shape.seq_len)
    c_sh = model.cache_shapes(shape.global_batch, shape.seq_len)
    c_specs = shd.specs_for_tree(c_ax, c_sh, rules=rules)
    return (shd.named_shardings(mesh, p_specs),
            shd.named_shardings(mesh, b_specs),
            shd.named_shardings(mesh, c_specs))
