"""Zamba2-7B's structure at a small size on the CPU: the program's serve
path (prefill, then decode through the cache) against the benchmark's
plain float32 reference (``bench/lib/zamba2_ref.py``) on its seeded
weights, and the tensor-parallel flash_decode path on four host devices.

The small size keeps what makes the block: two shared blocks used in turn
at irregular ``hybrid_layer_ids``, the concat input, per-invocation
adapters and linears, and two Mamba groups."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.lib import zamba2_ref  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import LM  # noqa: E402
from repro.models.layers import mlp  # noqa: E402
from repro.models.ssm import gated_group_rms_norm  # noqa: E402

#: published keys at the small size: 6 layers, blocks A, B, A before
#: layers 1, 3, 4; 8 Mamba heads of 16 in 2 groups; 4 attention heads of 32
SMALL = {"num_hidden_layers": 6, "hidden_size": 64, "mamba_expand": 2,
         "n_mamba_heads": 8, "mamba_headdim": 16, "mamba_ngroups": 2,
         "mamba_d_state": 16, "mamba_d_conv": 4, "chunk_size": 4,
         "hybrid_layer_ids": [1, 3, 4], "num_mem_blocks": 2,
         "attention_hidden_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 4, "attention_head_dim": 32,
         "intermediate_size": 128, "adapter_rank": 8, "vocab_size": 512}


def small_dims() -> dict:
    with open(os.path.join(ROOT, "bench", "configs", "zamba2-7b.json")) as f:
        dims = json.load(f)
    return {**dims, **SMALL}


def small_config(**edits):
    return get_config("zamba2-7b").replace(
        num_layers=6, d_model=64, num_heads=4, num_kv_heads=4, head_dim=32,
        d_ff=128, vocab_size=512, ssm_state=16, ssm_head_dim=16,
        ssm_groups=2, adapter_rank=8, hybrid_layer_ids=(1, 3, 4),
        chunk_size=4, **edits)


def serve(model, w, prompts, steps):
    """Prefill, then ``steps`` greedy decode steps through the cache, in
    float32: logits [B, steps + 1, V] and the served tokens."""
    b, p = prompts.shape
    v = model.cfg.vocab_size
    cache = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                         model.cache_shapes(b, p + steps + 1))
    logits, cache = jax.jit(model.prefill)(
        w, {"tokens": jnp.asarray(prompts)}, cache)
    got, toks = [logits[:, :v]], jnp.argmax(logits[:, :v], -1)
    served = [toks]
    step = jax.jit(model.decode_step)
    for i in range(steps):
        logits, cache = step(w, {"tokens": toks[:, None]}, cache,
                             jnp.int32(p + i))
        got.append(logits[:, :v])
        toks = jnp.argmax(logits[:, :v], -1)
        served.append(toks)
    return np.stack(got, 1), np.stack(served, 1)


def gap_to_reference(cfg, w32, dims, prompts, steps=5):
    """Largest |program - reference| logit over the prefill's last position
    and every decode step, both in float32."""
    got, served = serve(LM(cfg), w32, prompts, steps)
    p = prompts.shape[1]
    worst = 0.0
    for r in range(prompts.shape[0]):
        seq = np.concatenate([prompts[r], served[r, :-1]])
        ref = np.asarray(zamba2_ref.logits(w32, dims, seq))[p - 1:]
        worst = max(worst, float(np.abs(got[r] - ref).max()))
    return worst


@pytest.fixture(scope="module")
def weights():
    dims = small_dims()
    w = zamba2_ref.make_weights(dims, 2615000001)
    return dims, jax.tree.map(lambda a: a.astype(jnp.float32), w)


PROMPTS = np.random.default_rng(3).integers(0, 512, (2, 10))

#: float32 on both sides; what is left is summation order (the chunked
#: SSD against the stepwise recurrence, einsum against HIGHEST matmuls),
#: about 1e-5 on logits of order 1
TOL = 2e-4


def test_weights_match_the_programs_tree(weights):
    dims, w = weights
    model = LM(small_config())
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), model.shapes())
    got = jax.tree.map(lambda a: (a.shape, "bfloat16"), w)
    assert got == want


def test_program_matches_reference_in_float32(weights):
    """Prefill, then 5 decode steps through the stacked Mamba states and
    per-invocation KV caches, against the reference's full forward pass."""
    dims, w = weights
    assert gap_to_reference(small_config(), w, dims, PROMPTS) < TOL


def test_prefill_starts_from_a_zero_state(weights):
    """A server reuses one cache batch after batch: prefill overwrites the
    Mamba states and conv histories, whatever the cache held before."""
    dims, w = weights
    model = LM(small_config())
    b, p = PROMPTS.shape
    fresh = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                         model.cache_shapes(b, p + 4))
    stale = jax.tree.map(lambda a: jnp.full(a.shape, 0.5, jnp.float32),
                         fresh)
    prefill = jax.jit(model.prefill)
    tokens = {"tokens": jnp.asarray(PROMPTS)}
    (l1, c1), (l2, c2) = prefill(w, tokens, fresh), prefill(w, tokens, stale)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    for a, b_ in zip(jax.tree.leaves(c1["mamba"]), jax.tree.leaves(c2["mamba"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


@pytest.mark.parametrize("change", ["one_group", "no_adapters",
                                    "norm_then_gate"])
def test_reference_comparison_sees_a_missing_piece(weights, change,
                                                   monkeypatch):
    """The comparison is tight enough to catch the program leaving out a
    piece of the block: the group norm's groups (one norm over all heads),
    the adapters, or the gate's place (norm, then gate)."""
    dims, w = weights
    cfg = small_config()
    if change == "one_group":
        monkeypatch.setattr(
            "repro.models.ssm.gated_group_rms_norm",
            lambda y, z, scale, groups, eps, dtype:
            gated_group_rms_norm(y, z, scale, 1, eps, dtype))
    elif change == "no_adapters":
        monkeypatch.setattr(
            "repro.models.layers.mlp",
            lambda p, x, act="silu", adapter=None: mlp(p, x, act))
    else:
        monkeypatch.setattr("repro.models.ssm.gated_group_rms_norm",
                            _norm_then_gate)
    assert gap_to_reference(cfg, w, dims, PROMPTS, steps=2) > 100 * TOL


def _norm_then_gate(y, z, scale, groups, eps, dtype):
    b, t, nh, hd = y.shape
    yg = y.reshape(b, t, groups, nh // groups * hd).astype(jnp.float32)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
    yn = yg.reshape(b, t, nh * hd) * jax.nn.silu(
        z.reshape(b, t, nh * hd).astype(jnp.float32))
    return yn.astype(dtype) * scale


def test_zamba2_7b_registry_entry():
    cfg = get_config("zamba2-7b")
    assert (cfg.num_layers, cfg.d_model, cfg.ssm_heads, cfg.ssm_groups,
            cfg.d_inner) == (81, 3584, 112, 2, 7168)
    assert len(cfg.hybrid_layers) == 13 and cfg.shared_blocks == 2
    assert cfg.resolved_head_dim * cfg.num_heads == 2 * cfg.d_model
    n = LM(cfg).param_defs()
    from repro.models import param_count
    assert 7.2e9 < param_count(n) < 7.5e9


def test_zamba2_2_7b_keeps_its_structure():
    """zamba2-2.7b on the same path: one shared block over d_model before
    every 6th layer, no concat, no adapters, no per-invocation linear."""
    cfg = get_config("zamba2-2.7b")
    assert cfg.hybrid_layers == tuple(range(5, 54, 6))
    defs = LM(cfg).param_defs()
    assert list(defs["shared"]) == ["block0"] and "hybrid" not in defs
    assert defs["shared"]["block0"]["attn"]["wq"].shape[0] == cfg.d_model


_FOUR = textwrap.dedent("""
    import json, sys
    import jax, jax.numpy as jnp, numpy as np
    sys.path.insert(0, {root!r})
    from tests.test_zamba2 import small_config
    from repro.distributed import sharding as shd
    from repro.launch import steps as S
    from repro.launch.mesh import make_mesh_for
    from repro.configs import ShapeSpec
    from repro.models import LM

    cfg = small_config()
    mesh = make_mesh_for(4)
    assert dict(mesh.shape) == {{"data": 1, "model": 4}}
    jax.sharding.set_mesh(mesh)
    b, p, g = 2, 10, 4
    out = {{}}
    for flash in (False, True):
        model = LM(cfg.replace(use_flash=flash))
        shd.set_rules(S.rules_for(model.cfg))
        p_sh, _, c_sh = S.serve_shardings(model, mesh,
                                          ShapeSpec("s", p + g, b, "decode"))
        params = jax.jit(model.init, out_shardings=p_sh)(
            jax.random.PRNGKey(0))
        cache = jax.jit(lambda: model.init_cache(b, p + g),
                        out_shardings=c_sh)()
        toks = jnp.asarray(np.random.default_rng(1).integers(0, 512, (b, p)))
        logits, cache = jax.jit(model.prefill)(params, {{"tokens": toks}},
                                               cache)
        dec = jax.jit(model.decode_step)
        nxt = jnp.argmax(logits, -1)[:, None]
        jaxpr = str(jax.make_jaxpr(dec)(params, {{"tokens": nxt}}, cache,
                                        jnp.int32(p)))
        for i in range(g - 1):
            logits, cache = dec(params, {{"tokens": nxt}}, cache,
                                jnp.int32(p + i))
            nxt = jnp.argmax(logits, -1)[:, None]
        out[flash] = (np.asarray(logits, np.float32),
                      [np.asarray(a, np.float32)
                       for a in jax.tree.leaves(cache)],
                      "shard_map" in jaxpr,
                      [str(a) for a in cache["shared"]["k"].sharding.spec])
    (le, ce, sm_e, _), (lf, cf, sm_f, spec) = out[False], out[True]
    # rules that split the cache's head dim: the flash path refuses them
    shd.set_rules({{**S.rules_for(model.cfg), "cache_hd": "model",
                    "cache_heads": None}})
    try:
        # a new function: a trace cached under the old rules would serve
        jax.make_jaxpr(lambda *a: model.decode_step(*a))(
            params, {{"tokens": nxt}}, cache, jnp.int32(p))
        refused = False
    except ValueError:
        refused = True
    print(json.dumps({{
        "logits": float(np.abs(le - lf).max()),
        "cache": max(float(np.abs(a - b).max()) for a, b in zip(ce, cf)),
        "shard_map": [sm_e, sm_f], "kv_spec": spec, "refused": refused}}))
""")


def test_flash_decode_under_shard_map_on_four_devices():
    """On a (1, 4) mesh of host devices the decode step runs flash_decode
    (interpret mode) under ``shard_map`` over the KV heads, the cache
    sharded over its heads; its logits and cache match the einsum path.
    Rules that shard the cache's head dim are refused, not gathered."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([os.path.join(ROOT, "src"), ROOT])}
    res = subprocess.run([sys.executable, "-c", _FOUR.format(root=ROOT)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    got = json.loads(res.stdout.strip().splitlines()[-1])
    assert got["shard_map"] == [False, True]
    assert got["kv_spec"][:3] == ["None", "None", "model"]   # [13,B,KV,..]
    assert "model" not in got["kv_spec"][3:]
    assert got["refused"]
    # bfloat16 weights and cache: the two paths round differently
    assert got["logits"] < 2e-2 and got["cache"] < 2e-2


_SHARDED = textwrap.dedent("""
    import json, sys
    import jax, jax.numpy as jnp
    sys.path.insert(0, {root!r})
    from tests import test_zamba2 as Z
    from bench.lib import zamba2_ref
    from repro.configs import ShapeSpec
    from repro.distributed import sharding as shd
    from repro.launch import steps as S
    from repro.launch.mesh import make_mesh_for
    from repro.models import LM

    cfg = Z.small_config(use_flash=True)
    mesh = make_mesh_for(4)
    jax.sharding.set_mesh(mesh)
    shd.set_rules(S.rules_for(cfg))
    p_sh = S.serve_shardings(LM(cfg), mesh, ShapeSpec("s", 16, 2, "decode"))[0]
    dims = Z.small_dims()
    w = zamba2_ref.make_weights(dims, 2615000001, p_sh)
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    print(json.dumps({{
        "gap": Z.gap_to_reference(cfg, w32, dims, Z.PROMPTS),
        "sharded": sum("model" in str(a.sharding.spec)
                       for a in jax.tree.leaves(w32))}}))
""")


def test_sharded_program_matches_reference_in_float32():
    """The served path as the cell runs it, 4-way tensor parallel on a
    (1, 4) mesh of host devices with flash_decode (interpret mode) under
    ``shard_map``, in float32, against the reference: the same tolerance
    as on one device (the shards' partial sums change only the order of
    summation)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([os.path.join(ROOT, "src"), ROOT])}
    res = subprocess.run([sys.executable, "-c", _SHARDED.format(root=ROOT)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    got = json.loads(res.stdout.strip().splitlines()[-1])
    assert got["sharded"] > 10
    assert got["gap"] < TOL


def test_one_device_decode_has_no_shard_map():
    """On one device (granite's decode cell) the flash path calls the
    kernel directly: no ``shard_map`` in the decode step."""
    cfg = small_config(use_flash=True)
    model = LM(cfg)
    jaxpr = jax.make_jaxpr(model.decode_step)(
        model.shapes(), {"tokens": jax.ShapeDtypeStruct((2, 1), jnp.int32)},
        model.cache_shapes(2, 16), jnp.int32(3))
    assert "shard_map" not in str(jaxpr)
    assert "flash_decode" in str(jaxpr)
