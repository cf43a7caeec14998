"""Logical-axis sharding rules for the (pod, data, model) production mesh.

Every tensor in the framework (weights, activations, optimizer state, KV
caches) is annotated with *logical* axis names; this module resolves them to
``PartitionSpec``s against whatever physical mesh is active.  Hillclimb
levers (sequence parallelism, FSDP/ZeRO weight sharding, cache layout) are
rule edits here — model code never mentions a physical mesh axis.

Resolution is defensive by construction:

* a rule that names a mesh axis absent from the current mesh drops it
  (the same model code lowers on the single-pod and multi-pod meshes);
* a mesh axis whose size does not divide the tensor dimension is dropped
  for that tensor (e.g. 8 KV heads on a 16-way model axis fall back to
  replication exactly like Megatron does);
* one physical axis is never assigned twice in a spec.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axes = Tuple[Optional[str], ...]
PhysAxes = Union[None, str, Tuple[str, ...]]

# ---------------------------------------------------------------------------
# rule sets

#: baseline rules — Megatron-style TP over "model", batch over ("pod","data").
BASE_RULES: Dict[str, PhysAxes] = {
    "batch": ("pod", "data"),
    "seq": None,                 # sequence-parallel residual: set to "model"
    "embed": None,               # residual d_model
    "vocab": "model",
    "vocab_rep": None,           # input-embedding vocab rows (gather stays local)
    "embed_shard": "model",      # input-embedding feature dim
    "qkv": "model",              # flattened heads*head_dim projection axis
    "heads": "model",
    "head_dim": None,
    "mlp": "model",              # d_ff
    "expert": "model",
    "capacity": None,
    "layers": None,
    "ssm_inner": "model",        # mamba d_inner / rwkv projection axis
    "ssm_state": None,
    "ssm_heads": "model",
    "conv": None,
    "lora": None,
    "cache_batch": ("pod", "data"),
    "cache_seq": None,
    "cache_heads": None,
    "cache_hd": "model",         # decode KV cache sharded over head_dim
    "frames": None,
    "fsdp": None,                # weights' largest axis: set to "data" for ZeRO-3
}


def rules_with(**edits: PhysAxes) -> Dict[str, PhysAxes]:
    r = dict(BASE_RULES)
    r.update(edits)
    return r


#: sequence-parallel variant (activations' seq axis sharded over "model")
SP_RULES = rules_with(seq="model")
#: ZeRO-3 / FSDP variant (weight "fsdp"-tagged axes sharded over "data")
FSDP_RULES = rules_with(fsdp="data")

# ---------------------------------------------------------------------------
# active-rules context

_state = threading.local()


def set_rules(rules: Dict[str, PhysAxes]):
    _state.rules = dict(rules)


def get_rules() -> Dict[str, PhysAxes]:
    return getattr(_state, "rules", BASE_RULES)


@contextlib.contextmanager
def use_rules(rules: Dict[str, PhysAxes]):
    prev = get_rules()
    set_rules(rules)
    try:
        yield
    finally:
        set_rules(prev)


def _mesh_axis_sizes() -> Dict[str, int]:
    """Axis sizes of the mesh made active by ``jax.sharding.set_mesh``
    (empty when none is)."""
    return dict(jax.sharding.get_abstract_mesh().shape)


def resolve_spec(axes: Axes, rules: Optional[Dict[str, PhysAxes]] = None,
                 dims: Optional[Sequence[int]] = None) -> P:
    """Logical axes -> PartitionSpec under the active mesh and rules.

    When two dims of one tensor map to the same mesh axis, the first dim
    wins by default.  A rule set with ``"__reverse__": True`` resolves the
    LAST dim first instead — used by the zero3cp profile so weight matrices
    shard their OUTPUT dim (gather-at-use ZeRO-3) rather than their
    contraction dim (which would force output all-reduces).
    """
    rules = rules or get_rules()
    sizes = _mesh_axis_sizes()
    used: set = set()
    order = range(len(axes))
    if rules.get("__reverse__"):
        order = reversed(order)
    out: list = [None] * len(axes)
    for i in order:
        name = axes[i]
        phys = rules.get(name) if name else None
        cand = (phys,) if isinstance(phys, str) else (phys or ())
        keep = []
        prod = 1
        for ax in cand:
            if ax is None or ax in used or ax not in sizes:
                continue
            keep.append(ax)
            prod *= sizes[ax]
        if dims is not None and keep and prod and dims[i] % prod != 0:
            keep = []                      # indivisible -> replicate this dim
        used.update(keep)
        out[i] = tuple(keep) if len(keep) > 1 else (keep[0] if keep else None)
    return P(*out)


def shard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical axes (no-op outside a mesh)."""
    if jax.sharding.get_abstract_mesh().empty:
        return x
    spec = resolve_spec(tuple(axes), dims=x.shape)
    return jax.lax.with_sharding_constraint(x, spec)


def gather_weight(w: jax.Array) -> jax.Array:
    """ZeRO-3 explicit weight gather (active under rules with
    ``__gather_weights__``, e.g. the zero3cp profile).

    Constraining the stored (data x model)-sharded weight to replicated in
    the FORWARD makes XLA all-gather it once per use — and, crucially, the
    constraint's autodiff transpose REDUCE-SCATTERS the weight gradient back
    to the shard, so backward dgrad contracts over an unsharded weight
    (local) instead of emitting [B,S,D]-sized partial-sum all-reduces."""
    if (not get_rules().get("__gather_weights__")
            or jax.sharding.get_abstract_mesh().empty):
        return w
    return jax.lax.with_sharding_constraint(w, P(*([None] * w.ndim)))


def specs_for_tree(logical_tree: Any, shapes_tree: Any = None,
                   rules: Optional[Dict[str, PhysAxes]] = None) -> Any:
    """Map a tree of logical-axes tuples to PartitionSpecs."""
    is_axes = lambda x: isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x)
    if shapes_tree is None:
        return jax.tree.map(lambda a: resolve_spec(a, rules),
                            logical_tree, is_leaf=is_axes)
    return jax.tree.map(
        lambda a, s: resolve_spec(a, rules, dims=s.shape),
        logical_tree, shapes_tree, is_leaf=is_axes)


def named_shardings(mesh: Mesh, specs_tree: Any) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs_tree,
                        is_leaf=lambda x: isinstance(x, P))
