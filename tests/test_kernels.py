"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import (attention_ref, flash_attention,
                                           gqa_attention)
from repro.kernels.stencil import (GAUSS3, SHARPEN3, SOBEL_X3, gaussian_blur,
                                   stencil3x3, stencil3x3_ref)


# ---------------------------------------------------------------------------
# stencil


@pytest.mark.parametrize("h,w", [(8, 16), (100, 240), (128, 128), (77, 515)])
@pytest.mark.parametrize("kernel", [GAUSS3, SHARPEN3, SOBEL_X3])
def test_stencil_shapes(h, w, kernel):
    rng = np.random.default_rng(h * w)
    x = jnp.asarray(rng.normal(size=(h, w)).astype("float32"))
    np.testing.assert_allclose(stencil3x3(x, kernel),
                               stencil3x3_ref(x, kernel),
                               rtol=1e-5, atol=1e-5)


def test_stencil_bh_sweep():
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(300, 200)).astype("float32"))
    for bh in (32, 128, 256):
        np.testing.assert_allclose(stencil3x3(x, GAUSS3, bh=bh),
                                   stencil3x3_ref(x, GAUSS3),
                                   rtol=1e-5, atol=1e-5)


def test_gaussian_blur_matches_cgra_app_semantics():
    """kernels/stencil gaussian == the CGRA gaussian app's fixed-point math
    (up to the CGRA's >>4 truncation)."""
    rng = np.random.default_rng(3)
    img = rng.integers(0, 255, size=(12, 12)).astype(np.float32)
    blur = np.asarray(gaussian_blur(jnp.asarray(img), use_kernel=True))
    ref = np.asarray(gaussian_blur(jnp.asarray(img), use_kernel=False))
    np.testing.assert_allclose(blur, ref, rtol=1e-6)


# ---------------------------------------------------------------------------
# flash attention


@pytest.mark.parametrize("b,h,s,d", [(1, 1, 128, 64), (2, 4, 200, 64),
                                     (1, 2, 384, 128), (2, 1, 65, 32)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_shapes(b, h, s, d, causal):
    rng = np.random.default_rng(b * s + d)
    q, k, v = (jnp.asarray(rng.normal(size=(b, h, s, d)).astype("float32"))
               for _ in range(3))
    got = flash_attention(q, k, v, causal=causal)
    want = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-3), ("bfloat16", 4e-2)])
def test_flash_attention_dtypes(dtype, tol):
    rng = np.random.default_rng(11)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, 130, 64))).astype(dtype)
               for _ in range(3))
    got = flash_attention(q, k, v, causal=True)
    want = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_cross_lengths():
    """Skv != Sq (cross/cache shapes)."""
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 2, 64, 32)).astype("float32"))
    k = jnp.asarray(rng.normal(size=(1, 2, 200, 32)).astype("float32"))
    v = jnp.asarray(rng.normal(size=(1, 2, 200, 32)).astype("float32"))
    got = flash_attention(q, k, v, causal=False)
    want = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (8, 1)])
def test_gqa_head_grouping(hq, hkv):
    rng = np.random.default_rng(hq * 10 + hkv)
    q = jnp.asarray(rng.normal(size=(2, hq, 96, 32)).astype("float32"))
    k = jnp.asarray(rng.normal(size=(2, hkv, 96, 32)).astype("float32"))
    v = jnp.asarray(rng.normal(size=(2, hkv, 96, 32)).astype("float32"))
    got = gqa_attention(q, k, v, causal=True)
    rep = hq // hkv
    want = attention_ref(q, jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1),
                         causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# flash decode (single-token cache attention)


@pytest.mark.parametrize("b,kv,g,t,hd,bk", [
    (2, 4, 2, 300, 64, 128), (1, 8, 4, 512, 128, 256),
    (3, 2, 1, 100, 32, 64), (1, 1, 8, 70, 64, 128)])
def test_flash_decode_shapes(b, kv, g, t, hd, bk):
    from repro.kernels.flash_decode import flash_decode, flash_decode_ref
    rng = np.random.default_rng(b * t + hd)
    q = jnp.asarray(rng.normal(size=(b, kv, g, hd)).astype("float32"))
    k = jnp.asarray(rng.normal(size=(b, kv, hd, t)).astype("float32"))
    v = jnp.asarray(rng.normal(size=(b, kv, hd, t)).astype("float32"))
    lens = jnp.asarray(rng.integers(1, t, size=(b,)).astype("int32"))
    got = flash_decode(q, k, v, lens, bk=bk)
    want = flash_decode_ref(q, k, v, lens)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_flash_decode_bf16():
    from repro.kernels.flash_decode import flash_decode, flash_decode_ref
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(2, 2, 4, 64))).astype(jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, 2, 64, 200))).astype(jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(2, 2, 64, 200))).astype(jnp.bfloat16)
    lens = jnp.asarray([150, 37], jnp.int32)
    got = flash_decode(q, k, v, lens)
    want = flash_decode_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=4e-2, atol=4e-2)


def test_flash_decode_matches_model_cache_attention():
    """The kernel reproduces the model's einsum cache-attention math."""
    from repro.kernels.flash_decode import flash_decode_ref
    rng = np.random.default_rng(2)
    b, kv, g, t, hd = 2, 2, 2, 64, 32
    q = jnp.asarray(rng.normal(size=(b, 1, kv, g, hd)).astype("float32"))
    ck = jnp.asarray(rng.normal(size=(b, kv, hd, t)).astype("float32"))
    cv = jnp.asarray(rng.normal(size=(b, kv, hd, t)).astype("float32"))
    pos = 40
    # model path (layers.attention cache branch math)
    import math as _m
    sc = jnp.einsum("bskgd,bkdt->bkgst", q, ck) / _m.sqrt(hd)
    mask = (jnp.arange(t) < pos + 1)[None, None, None, None, :]
    pr = jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1)
    want = jnp.einsum("bkgst,bkdt->bskgd", pr, cv)[:, 0]
    got = flash_decode_ref(q[:, 0], ck, cv,
                           jnp.full((b,), pos + 1, jnp.int32))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("frontier", ["one", "mid_block", "full"])
@pytest.mark.parametrize("t,bk", [(512, 256), (300, 128)])
def test_flash_decode_stacked_cache_equals_layer_slice(t, bk, frontier):
    """The stacked [L, B, KV, hd, T] cache read at a layer index gives the
    4-D call on that layer's slice bit for bit, when bk divides T and when
    the last block is partial."""
    from repro.kernels.flash_decode import flash_decode
    rng = np.random.default_rng(t + bk)
    n_layers, b, kv, g, hd = 3, 2, 2, 4, 64
    q = jnp.asarray(rng.normal(size=(b, kv, g, hd)).astype("float32"))
    k, v = (jnp.asarray(rng.normal(size=(n_layers, b, kv, hd, t))
                        .astype("float32")) for _ in range(2))
    at = {"one": 1, "mid_block": (t // bk) * bk - bk // 2, "full": t}[frontier]
    lens = jnp.asarray([at, max(1, at - 1)], jnp.int32)
    for layer in range(n_layers):
        got = flash_decode(q, k, v, lens, jnp.int32(layer), bk=bk)
        want = flash_decode(q, k[layer], v[layer], lens, bk=bk)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("t,bk", [(512, 256), (512, 128), (300, 128),
                                  (640, None)])
def test_flash_decode_ignores_the_cache_past_the_frontier(t, bk, stacked):
    """Every cache column at or past a row's frontier (the live block's
    tail, whole blocks past it, a partial last block's) holds NaN: the
    output equals the oracle's on the clean cache, for rows of different
    lengths, one of 1 and one on a block boundary."""
    from repro.kernels.flash_decode import flash_decode, flash_decode_ref
    rng = np.random.default_rng(t + (bk or 0) + stacked)
    n_layers, b, kv, g, hd, layer = 3, 4, 2, 4, 64, 1
    step = bk or 128
    lens = np.array([1, step, step + 37, t], np.int32)
    q = jnp.asarray(rng.normal(size=(b, kv, g, hd)).astype("float32"))
    k, v = (rng.normal(size=(n_layers, b, kv, hd, t)).astype("float32")
            for _ in range(2))
    past = np.arange(t)[None, :] >= lens[:, None]            # [B, T]
    past = np.broadcast_to(past[None, :, None, None, :], k.shape)
    dirty_k, dirty_v = np.where(past, np.nan, k), np.where(past, np.nan, v)
    want = flash_decode_ref(q, jnp.asarray(k[layer]), jnp.asarray(v[layer]),
                            jnp.asarray(lens))
    if stacked:
        got = flash_decode(q, jnp.asarray(dirty_k), jnp.asarray(dirty_v),
                           jnp.asarray(lens), jnp.int32(layer), bk=bk)
    else:
        got = flash_decode(q, jnp.asarray(dirty_k[layer]),
                           jnp.asarray(dirty_v[layer]), jnp.asarray(lens),
                           bk=bk)
    assert np.all(np.isfinite(np.asarray(got)))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kv,g,hd,t,want_bk", [
    (8, 2, 64, 1536, 512),            # granite-moe-1b-a400m decode
    (8, 1, 224, 2048, None),          # zamba2-7b, one chip's 8 KV heads
    (8, 2, 64, 4096, None),           # granite prefill's longest bucket
    (8, 2, 64, 1056, None),           # ... its shortest, a partial block
    (2, 4, 64, 100, 100)])            # shorter than a block: T itself
def test_flash_decode_plan_fits_vmem(kv, g, hd, t, want_bk):
    """decode_plan takes the largest block of 128 x 2^j columns (or T)
    within the VMEM budget, and tiles T with kv_steps blocks."""
    from repro.kernels.flash_decode.flash_decode import (VMEM_BUDGET,
                                                         decode_plan,
                                                         step_vmem_bytes)
    bk, steps = decode_plan(kv, g, hd, t, 2)
    assert steps == -(-t // bk)
    assert step_vmem_bytes(kv, g, hd, bk, 2) <= VMEM_BUDGET
    if want_bk is not None:
        assert bk == want_bk
    if bk < t:
        assert bk % 128 == 0
        assert step_vmem_bytes(kv, g, hd, 2 * bk, 2) > VMEM_BUDGET


def test_blockwise_matches_flash_and_ref():
    """The model's jnp blockwise attention is a third implementation of the
    same math — all three must agree."""
    from repro.models.layers import _blockwise_attention
    rng = np.random.default_rng(9)
    b, hkv, g, s, d = 1, 2, 2, 160, 32
    q = jnp.asarray(rng.normal(size=(b, s, hkv, g, d)).astype("float32"))
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)).astype("float32"))
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)).astype("float32"))
    got = _blockwise_attention(q, k, v, causal=True, bq=64, bk=64)
    # reference: repeat kv heads, use attention_ref layout [B,H,S,d]
    qh = jnp.moveaxis(q.reshape(b, s, hkv * g, d), 1, 2)
    kh = jnp.moveaxis(jnp.repeat(k, g, axis=2), 1, 2)
    vh = jnp.moveaxis(jnp.repeat(v, g, axis=2), 1, 2)
    want = attention_ref(qh, kh, vh, causal=True)
    want = jnp.moveaxis(want, 2, 1).reshape(b, s, hkv, g, d)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
