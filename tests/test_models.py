"""Per-architecture smoke tests (deliverable f) + model-level invariants.

Every assigned architecture instantiates a REDUCED config of the same family
and runs a real forward/train step on CPU, asserting output shapes and
finite values.  The FULL configs are exercised only via the dry-run.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, cell_is_runnable, get_config
from repro.models import LM, param_count
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update

ARCH_NAMES = sorted(ARCHS)


def _smoke_batch(cfg, b=2, s=16, seed=0):
    rng = jax.random.PRNGKey(seed)
    toks = jax.random.randint(rng, (b, s), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    if cfg.family == "vlm":
        batch["image_embeds"] = 0.1 * jnp.ones(
            (b, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.family == "audio":
        batch["frames"] = 0.1 * jnp.ones((b, 1500, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_smoke_forward_shapes_and_finite(arch):
    cfg = get_config(arch).smoke()
    m = LM(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = _smoke_batch(cfg)
    logits, aux = m.forward(params, batch)
    assert logits.shape == (2, 16, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert bool(jnp.isfinite(aux))


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_smoke_train_step_no_nans(arch):
    cfg = get_config(arch).smoke()
    m = LM(cfg)
    params = m.init(jax.random.PRNGKey(0))
    opt_cfg = AdamWConfig(lr=1e-3)
    opt = adamw_init(params, opt_cfg)
    batch = _smoke_batch(cfg)

    def loss_fn(p):
        return m.loss(p, batch)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert bool(jnp.isfinite(loss)), f"{arch}: loss {loss}"
    p2, opt2 = adamw_update(params, grads, opt, opt_cfg)
    for leaf in jax.tree.leaves(p2):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))
    loss2 = loss_fn(p2)
    assert bool(jnp.isfinite(loss2))


@pytest.mark.slow          # ~30 s across archs: the worst fast-lane offender
@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_prefill_decode_matches_forward(arch):
    cfg = get_config(arch).smoke()
    if cfg.num_experts:
        # exact equality needs (a) no capacity drops and (b) no DISCRETE
        # routing choices: near-tied top-k picks flip on bf16 fusion
        # differences between the two paths (a routing discontinuity, not a
        # cache bug).  Routing to all experts keeps the full dispatch /
        # combine machinery while making the layer continuous.
        cfg = cfg.replace(capacity_factor=8.0,
                          experts_per_token=cfg.num_experts)
    m = LM(cfg)
    params = m.init(jax.random.PRNGKey(0))
    b, s = 2, 16
    batch = _smoke_batch(cfg, b, s)
    full, _ = m.forward(params, batch)
    cache = m.init_cache(b, s + 4)
    pb = dict(batch)
    pb.pop("labels")
    pb["tokens"] = batch["tokens"][:, :s - 1]
    lg_pre, cache = m.prefill(params, pb, cache)
    lg_dec, cache = m.decode_step(
        params, {"tokens": batch["tokens"][:, s - 1:s]}, cache,
        jnp.int32(s - 1))
    np.testing.assert_allclose(
        np.asarray(lg_pre, np.float32), np.asarray(full[:, s - 2], np.float32),
        rtol=0.05, atol=0.05)
    np.testing.assert_allclose(
        np.asarray(lg_dec, np.float32), np.asarray(full[:, s - 1], np.float32),
        rtol=0.05, atol=0.05)


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_full_config_param_defs_match_analytic_count(arch):
    """ParamDef tree of the FULL config (no allocation) is within 2% of the
    analytic parameter count used for MODEL_FLOPS."""
    cfg = get_config(arch)
    m = LM(cfg)
    defs_n = param_count(m.param_defs())
    analytic = cfg.param_count()
    # padded vocab / lora towers cause small deviations
    assert abs(defs_n - analytic) / analytic < 0.06, (defs_n, analytic)


def test_moe_capacity_drops_are_bounded():
    """At capacity_factor=1.25, dropped-token fraction stays small."""
    cfg = get_config("granite-moe-1b-a400m").smoke()
    m = LM(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = _smoke_batch(cfg, b=4, s=32)
    logits, aux = m.forward(params, batch)
    assert bool(jnp.isfinite(aux))
    # load-balance loss is ~1 at uniform routing; random init on a tiny
    # config routes unevenly, bounded well below pathological collapse (=E)
    assert float(aux) < 8.0


def test_rwkv_chunked_matches_stepwise():
    """Chunked WKV == exact per-token recurrence."""
    from repro.models.ssm import rwkv_wkv_chunked
    rng = np.random.default_rng(0)
    b, t, nh, hd = 2, 24, 2, 8
    r, k, v = (jnp.asarray(rng.normal(size=(b, t, nh, hd)).astype("float32"))
               for _ in range(3))
    w_log = -jnp.asarray(rng.uniform(0.05, 1.5, size=(b, t, nh, hd))
                         .astype("float32"))
    u = jnp.asarray(rng.normal(size=(nh, hd)).astype("float32"))
    s0 = jnp.zeros((b, nh, hd, hd), jnp.float32)
    out_c, st_c = rwkv_wkv_chunked(r, k, v, w_log, u, s0, chunk=8)
    out_1, st_1 = rwkv_wkv_chunked(r, k, v, w_log, u, s0, chunk=1)
    np.testing.assert_allclose(out_c, out_1, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(st_c, st_1, rtol=1e-4, atol=1e-4)


def test_mamba_chunked_matches_stepwise():
    from repro.models.ssm import mamba_ssd_chunked
    rng = np.random.default_rng(1)
    b, t, nh, hd, st = 2, 24, 2, 8, 4
    xh = jnp.asarray(rng.normal(size=(b, t, nh, hd)).astype("float32"))
    # B and C per head (each head's group's)
    B = jnp.asarray(rng.normal(size=(b, t, nh, st)).astype("float32"))
    C = jnp.asarray(rng.normal(size=(b, t, nh, st)).astype("float32"))
    logA = -jnp.asarray(rng.uniform(0.05, 1.0, size=(b, t, nh))
                        .astype("float32"))
    s0 = jnp.zeros((b, nh, hd, st), jnp.float32)
    out_c, st_c = mamba_ssd_chunked(xh, B, C, logA, s0, chunk=8)
    out_1, st_1 = mamba_ssd_chunked(xh, B, C, logA, s0, chunk=1)
    np.testing.assert_allclose(out_c, out_1, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(st_c, st_1, rtol=1e-4, atol=1e-4)


def test_scan_vs_unrolled_same_logits():
    """scan_layers=False (the dry-run probe path) is numerically identical."""
    cfg = get_config("llama3-8b").smoke()
    m1 = LM(cfg)
    m2 = LM(cfg.replace(scan_layers=False))
    params = m1.init(jax.random.PRNGKey(0))
    batch = _smoke_batch(cfg)
    l1, _ = m1.forward(params, batch)
    l2, _ = m2.forward(params, batch)
    np.testing.assert_allclose(np.asarray(l1, np.float32),
                               np.asarray(l2, np.float32), rtol=8e-2,
                               atol=8e-2)


def test_flash_impl_matches_einsum_in_model():
    """The Pallas flash path (attn_impl='flash', interpret mode) agrees
    with the einsum path inside the full model."""
    cfg = get_config("llama3-8b").smoke()
    m_e = LM(cfg.replace(attn_impl="einsum"))
    m_f = LM(cfg.replace(attn_impl="flash"))
    params = m_e.init(jax.random.PRNGKey(0))
    batch = _smoke_batch(cfg)
    le, _ = m_e.forward(params, batch)
    lf, _ = m_f.forward(params, batch)
    np.testing.assert_allclose(np.asarray(le, np.float32),
                               np.asarray(lf, np.float32),
                               rtol=6e-2, atol=6e-2)


def test_blockwise_impl_matches_einsum_in_model():
    cfg = get_config("qwen2.5-14b").smoke()   # qkv_bias exercises biases
    m_e = LM(cfg.replace(attn_impl="einsum"))
    m_b = LM(cfg.replace(attn_impl="blockwise"))
    params = m_e.init(jax.random.PRNGKey(0))
    batch = _smoke_batch(cfg)
    le, _ = m_e.forward(params, batch)
    lb, _ = m_b.forward(params, batch)
    np.testing.assert_allclose(np.asarray(le, np.float32),
                               np.asarray(lb, np.float32),
                               rtol=6e-2, atol=6e-2)


@pytest.mark.parametrize("arch,edits", [
    ("llama3-8b", {}), ("granite-moe-1b-a400m", {}),
    ("granite-moe-1b-a400m", {"moe_layer_period": 2}),
    ("llama3-8b", {"scan_layers": False})])
def test_flash_decode_kernel_in_model_decode(arch, edits):
    """use_flash routes single-token decode through the Pallas flash-decode
    kernel, with the stacked cache carried through the layer scan; logits
    must match the einsum cache path, and so must the updated cache."""
    cfg = get_config(arch).replace(**edits).smoke()
    m_e, m_f = LM(cfg), LM(cfg.replace(use_flash=True))
    params = m_e.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    c1, c2 = m_e.init_cache(2, 20), m_f.init_cache(2, 20)
    _, c1 = m_e.prefill(params, {"tokens": toks[:, :15]}, c1)
    _, c2 = m_f.prefill(params, {"tokens": toks[:, :15]}, c2)
    d1, c1 = m_e.decode_step(params, {"tokens": toks[:, 15:]}, c1,
                             jnp.int32(15))
    d2, c2 = m_f.decode_step(params, {"tokens": toks[:, 15:]}, c2,
                             jnp.int32(15))
    np.testing.assert_allclose(np.asarray(d1, np.float32),
                               np.asarray(d2, np.float32),
                               rtol=2e-2, atol=2e-2)
    for a, b in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-2)


def test_cell_runnability_covers_40():
    """44 cells (the 40 assigned, and zamba2-7b's 4): count runnable +
    documented skips."""
    total = runnable = 0
    for arch, cfg in ARCHS.items():
        for shape in SHAPES.values():
            total += 1
            ok, why = cell_is_runnable(cfg, shape)
            if ok:
                runnable += 1
            else:
                assert "long_500k" in why or "sub-quadratic" in why
    assert total == 44
    assert runnable == 36          # 8 documented long_500k skips
