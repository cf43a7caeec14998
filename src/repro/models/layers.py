"""Transformer building blocks shared by all assigned architectures.

Everything is a pure function over a params subtree (built by the matching
``*_defs`` builder).  Activations carry logical sharding constraints from
``repro.distributed.sharding`` so the same code lowers on 1 CPU device and
on the 512-chip production mesh.

Attention has three interchangeable implementations:

* ``einsum``     — full-score XLA path (short sequences, decode)
* ``blockwise``  — online-softmax over KV blocks via lax.scan; memory-bounded,
                   backend-agnostic (the 32k prefill default)
* ``flash``      — the Pallas TPU kernel (kernels/flash_attention)

MoE uses per-sequence grouped routing with fixed expert capacity: tokens are
sorted by expert id along the (unsharded) sequence axis, gathered into a
dense [batch, expert, capacity, d] block, run through expert FFNs with the
expert axis model-sharded, and combined by a token-side gather.  This is
gather-only (no scatter), which GSPMD partitions cleanly.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import gather_weight as GW, resolve_spec, shard
from repro.kernels.flash_attention import gqa_attention
from .params import ParamDef

Tree = Dict[str, Any]

# ---------------------------------------------------------------------------
# norms


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * scale + bias


def norm_defs(d: int, with_bias: bool = False,
              prefix: Tuple[int, ...] = ()) -> Tree:
    ax = ("layers",) * len(prefix)
    out = {"scale": ParamDef(prefix + (d,), ax + ("embed",), init="ones")}
    if with_bias:
        out["bias"] = ParamDef(prefix + (d,), ax + ("embed",), init="zeros")
    return out


def apply_norm(p: Tree, x: jax.Array, eps: float) -> jax.Array:
    if "bias" in p:
        return layer_norm(x, p["scale"], p["bias"], eps)
    return rms_norm(x, p["scale"], eps)


# ---------------------------------------------------------------------------
# rotary embeddings


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, H, hd]; positions: [B, S] (absolute token indices)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) *
                    jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs      # [B, S, half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention


def attention_defs(cfg, d_model: Optional[int] = None, layers: int = 0,
                   d_in: Optional[int] = None) -> Tree:
    """q/k/v from inputs of width ``d_in`` (default ``d_model``), the
    output projected to ``d_model``."""
    d = d_model or cfg.d_model
    d_in = d_in or d
    hd = cfg.resolved_head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    pre = (layers,) if layers else ()
    ax = ("layers",) if layers else ()
    out = {
        "wq": ParamDef(pre + (d_in, hq * hd), ax + ("embed", "qkv")),
        "wk": ParamDef(pre + (d_in, hkv * hd), ax + ("embed", "qkv")),
        "wv": ParamDef(pre + (d_in, hkv * hd), ax + ("embed", "qkv")),
        "wo": ParamDef(pre + (hq * hd, d), ax + ("qkv", "embed"),
                       scale=1.0 / max(1, 2 * cfg.num_layers) ** 0.5),
    }
    if cfg.qkv_bias:
        for n, w in (("bq", hq), ("bk", hkv), ("bv", hkv)):
            out[n] = ParamDef(pre + (w * hd,), ax + ("qkv",), init="zeros")
    return out


def _causal_scores(q, k, *, causal: bool, q_off) -> jax.Array:
    """q [B,S,KV,G,hd] x k [B,T,KV,hd] -> masked fp32 scores [B,KV,G,S,T]."""
    hd = q.shape[-1]
    s = jnp.einsum("bskgd,btkd->bkgst", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        rows = q_off + jnp.arange(sq)[:, None]
        cols = jnp.arange(sk)[None, :]
        s = jnp.where(rows >= cols, s, -1e30)
    return s


def _einsum_attention(q, k, v, *, causal: bool, q_off=0) -> jax.Array:
    s = _causal_scores(q, k, causal=causal, q_off=q_off)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


def _blockwise_attention(q, k, v, *, causal: bool, bq: int = 512,
                         bk: int = 512) -> jax.Array:
    """Online-softmax attention, lax.map over Q blocks, scan over KV blocks."""
    b, sq, kvh, g, hd = q.shape
    skv = k.shape[1]
    sqp, skp = -(-sq // bq) * bq, -(-skv // bk) * bk
    qp = jnp.pad(q, ((0, 0), (0, sqp - sq), (0, 0), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, skp - skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, skp - skv), (0, 0), (0, 0)))
    nq, nk = sqp // bq, skp // bk
    qb = jnp.moveaxis(qp.reshape(b, nq, bq, kvh, g, hd), 1, 0)
    kb = jnp.moveaxis(kp.reshape(b, nk, bk, kvh, hd), 1, 0)
    vb = jnp.moveaxis(vp.reshape(b, nk, bk, kvh, hd), 1, 0)
    scale = 1.0 / math.sqrt(hd)

    def one_q(args):
        qi, qt = args                                   # [], [b,bq,kvh,g,hd]

        def kv_step(carry, kv):
            m, l, acc = carry
            ki, kt, vt = kv
            s = jnp.einsum("bskgd,btkd->bkgst", qt, kt,
                           preferred_element_type=jnp.float32) * scale
            rows = qi * bq + jnp.arange(bq)[:, None]
            cols = ki * bk + jnp.arange(bk)[None, :]
            mask = cols < skv
            if causal:
                mask = mask & (rows >= cols)
            s = jnp.where(mask, s, -1e30)
            m2 = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m2)
            p = jnp.exp(s - m2[..., None])
            l2 = l * alpha + jnp.sum(p, axis=-1)
            acc2 = acc * alpha[..., None] + jnp.einsum(
                "bkgst,btkd->bkgsd", p, vt.astype(jnp.float32))
            return (m2, l2, acc2), None

        m0 = jnp.full((b, kvh, g, bq), -1e30, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, bq), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, bq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kb, vb))
        l = jnp.where(l == 0.0, 1.0, l)
        out = acc / l[..., None]                        # [b,kvh,g,bq,hd]
        return jnp.moveaxis(out, 3, 1).reshape(b, bq, kvh, g, hd)

    blocks = jax.lax.map(one_q, (jnp.arange(nq), qb))   # [nq,b,bq,kvh,g,hd]
    out = jnp.moveaxis(blocks, 0, 1).reshape(b, sqp, kvh, g, hd)
    return out[:, :sq].astype(q.dtype)


@jax.named_scope("attention")
def attention(p: Tree, x: jax.Array, cfg, *, positions: jax.Array,
              causal: bool = True, memory: Optional[jax.Array] = None,
              cache: Optional[Tree] = None, cache_pos=None, cache_layer=None,
              impl: str = "einsum") -> Tuple[jax.Array, Optional[Tree]]:
    """Self- or cross-attention with optional KV cache.

    x: [B, S, D].  memory: [B, T, D] for cross-attention (keys/values come
    from memory and are not rope'd or cached causally).  cache: dict with
    "k"/"v" [B, KV, hd, T] updated at cache_pos: T is last, because for a
    64-wide head dim the chip's default layout of [.., T, hd] puts T minor
    anyway, and with T last that layout is row-major, so the flash_decode
    kernel reads the cache without a relayout.  With ``cache_layer`` cache
    holds every layer's [L, B, KV, hd, T]: this layer's tokens are written
    in place at that index, and the kernel reads the layer where it lies.
    The softmax scale is ``cfg.attn_scale_mult / sqrt(hd)`` (the multiplier
    goes into q, as every path below divides by sqrt(hd)); x's width may
    differ from the output's (``attention_defs``' ``d_in``).
    """
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    g = hq // hkv

    q = x @ GW(p["wq"])
    src = x if memory is None else memory
    k = src @ GW(p["wk"])
    v = src @ GW(p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = shard(q, "batch", "seq", "qkv")
    q = q.reshape(b, s, hq, hd)
    k = k.reshape(b, src.shape[1], hkv, hd)
    v = v.reshape(b, src.shape[1], hkv, hd)

    if memory is None:
        q = rope(q, positions, cfg.rope_theta)
        kpos = positions if cache is None else (
            cache_pos + jnp.arange(k.shape[1])[None, :])
        k = rope(k, kpos, cfg.rope_theta)
    if cfg.attn_scale_mult != 1.0:
        q = (q.astype(jnp.float32) * cfg.attn_scale_mult).astype(q.dtype)

    new_cache = None
    if cache is not None:
        kc = jnp.moveaxis(k, 1, 3).astype(cache["k"].dtype)   # [B,KV,hd,S]
        vc = jnp.moveaxis(v, 1, 3).astype(cache["v"].dtype)
        at = (0, 0, 0, cache_pos)
        if cache_layer is not None:
            kc, vc, at = kc[None], vc[None], (cache_layer,) + at
        with jax.named_scope("kv_update"):
            ck = jax.lax.dynamic_update_slice(cache["k"], kc, at)
            cv = jax.lax.dynamic_update_slice(cache["v"], vc, at)
        new_cache = {"k": ck, "v": cv}
        # causal masking against absolute positions: queries sit at
        # cache_pos..cache_pos+s-1, keys at 0..T-1
        q_off = cache_pos
    else:
        q_off = 0

    qg = q.reshape(b, s, hkv, g, hd)
    if cache is not None and s == 1 and cfg.use_flash and memory is None:
        # single-token decode through the Pallas flash-decode kernel:
        # streams the cache through VMEM once, no HBM score traffic
        lens = jnp.full((b,), 0, jnp.int32) + (cache_pos + 1)
        out = _flash_decode(qg[:, 0], ck, cv, lens,
                            cache_layer)[:, None]            # [B,1,KV,G,hd]
    elif cache is not None:
        # attention directly in cache layout [B, KV, hd, T]: transposing
        # the full cache (moveaxis) would read+write it twice per step,
        # which dominates decode HBM traffic
        if cache_layer is not None:
            ck = jax.lax.dynamic_index_in_dim(ck, cache_layer, 0, False)
            cv = jax.lax.dynamic_index_in_dim(cv, cache_layer, 0, False)
        sc = jnp.einsum("bkdt,bskgd->bkgst", ck, qg,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
        t = ck.shape[-1]
        rows = q_off + jnp.arange(s)[:, None]
        cols = jnp.arange(t)[None, :]
        mask = cols < (cache_pos + s)            # frontier
        if causal:
            mask = mask & (rows >= cols)
        sc = jnp.where(mask[None, None, None], sc, -1e30)
        pr = jax.nn.softmax(sc, axis=-1)
        out = jnp.einsum("bkgst,bkdt->bskgd", pr,
                         cv.astype(jnp.float32)).astype(x.dtype)
    elif impl == "flash":
        o = gqa_attention(jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
                          jnp.moveaxis(v, 1, 2), causal=causal)
        out = jnp.moveaxis(o, 1, 2).reshape(b, s, hkv, g, hd)
    elif impl == "blockwise":
        out = _blockwise_attention(qg, k, v, causal=causal)
    else:
        out = _einsum_attention(qg, k, v, causal=causal, q_off=q_off)

    out = out.reshape(b, s, hq * hd)
    out = shard(out, "batch", "seq", "qkv")
    y = out @ GW(p["wo"])
    return shard(y, "batch", "seq", "embed"), new_cache


def _flash_decode(q, k_cache, v_cache, lengths, layer):
    """The flash_decode kernel, on each chip's share of the KV heads when
    the mesh's ``model`` axis is larger than 1: Mosaic kernels cannot be
    partitioned automatically, so a ``shard_map`` over the cache's batch
    and head axes (as the sharding rules place them) gives each chip its
    own heads' rows of the cache, whole in head dim and length (the
    rules ``launch.steps.rules_for`` gives a flash config).  A cache whose
    head dim or length is sharded is refused: the kernel would need it
    gathered whole on every chip, at every layer."""
    from repro.kernels.flash_decode import flash_decode
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or dict(mesh.shape).get("model", 1) == 1:
        return flash_decode(q, k_cache, v_cache, lengths, layer)
    if any(resolve_spec(("cache_hd", "cache_seq"),
                        dims=k_cache.shape[-2:])):
        raise ValueError("flash_decode on a mesh needs the KV cache sharded "
                         "over its heads only (rules_for with use_flash)")
    b, kv = q.shape[:2]
    bat, heads = resolve_spec(("cache_batch", "cache_heads"), dims=(b, kv))
    q_spec = P(bat, heads, None, None)
    c_spec = P(*([None] * (k_cache.ndim - 4)), bat, heads, None, None)
    args, specs = (q, k_cache, v_cache, lengths), (q_spec, c_spec, c_spec,
                                                   P(bat))
    if layer is not None:
        args, specs = args + (layer,), specs + (P(),)
    return jax.shard_map(flash_decode, in_specs=specs, out_specs=q_spec,
                         check_vma=False)(*args)


# ---------------------------------------------------------------------------
# MLP


def mlp_defs(cfg, gated: bool = True, layers: int = 0,
             d_ff: Optional[int] = None) -> Tree:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    pre = (layers,) if layers else ()
    ax = ("layers",) if layers else ()
    out = {
        "w_up": ParamDef(pre + (d, f), ax + ("embed", "mlp")),
        "w_down": ParamDef(pre + (f, d), ax + ("mlp", "embed"),
                           scale=1.0 / max(1, 2 * cfg.num_layers) ** 0.5),
    }
    if gated:
        out["w_gate"] = ParamDef(pre + (d, f), ax + ("embed", "mlp"))
    return out


ACTS = {"silu": jax.nn.silu,
        "gelu": functools.partial(jax.nn.gelu, approximate=False)}


def adapter_defs(cfg, invocations: int) -> Tree:
    """Per-invocation rank-r LoRA on a gated MLP's gate and up projections
    (zamba2's shared-MLP adapters): x -> (x a) gate, (x a) up."""
    d, f, r = cfg.d_model, cfg.d_ff, cfg.adapter_rank
    pre, ax = (invocations,), ("layers",)
    return {"a": ParamDef(pre + (d, r), ax + ("embed", "lora")),
            "gate": ParamDef(pre + (r, f), ax + ("lora", "mlp")),
            "up": ParamDef(pre + (r, f), ax + ("lora", "mlp"))}


def mlp(p: Tree, x: jax.Array, act: str = "silu",
        adapter: Optional[Tree] = None) -> jax.Array:
    up = x @ GW(p["w_up"])
    if "w_gate" in p:
        gate = x @ GW(p["w_gate"])
        if adapter is not None:
            with jax.named_scope("adapter"):
                a = x @ adapter["a"]
                gate = gate + a @ adapter["gate"]
                up = up + a @ adapter["up"]
        h = ACTS[act](gate) * up
    else:
        h = jax.nn.gelu(up)
    h = shard(h, "batch", "seq", "mlp")
    return shard(h @ GW(p["w_down"]), "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# MoE (capacity-based grouped routing, gather-only dataflow)


def moe_defs(cfg, layers: int = 0) -> Tree:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    pre = (layers,) if layers else ()
    ax = ("layers",) if layers else ()
    return {
        "router": ParamDef(pre + (d, e), ax + ("embed", None),
                           dtype=jnp.float32),
        "w_gate": ParamDef(pre + (e, d, f), ax + ("expert", "embed", "mlp")),
        "w_up": ParamDef(pre + (e, d, f), ax + ("expert", "embed", "mlp")),
        "w_down": ParamDef(pre + (e, f, d), ax + ("expert", "mlp", "embed"),
                           scale=1.0 / max(1, 2 * cfg.num_layers) ** 0.5),
    }


@jax.named_scope("moe")
def moe_ffn(p: Tree, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """Returns (output, load-balance aux loss).  Routing groups = sequences:
    the sort/capacity bookkeeping runs along the unsharded seq axis, so
    dispatch is pure batched gathers under GSPMD."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    cap = int(math.ceil(s * k / e * cfg.capacity_factor))

    logits = (x.astype(jnp.float32) @ p["router"])            # [B,S,E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, choice = jax.lax.top_k(probs, k)                   # [B,S,k]
    gates = gates / jnp.clip(jnp.sum(gates, -1, keepdims=True), 1e-9)

    # load-balance aux (Switch): e * sum_e f_e * p_e
    density = jnp.mean(jax.nn.one_hot(choice[..., 0], e), axis=(0, 1))
    p_mean = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(density * p_mean)

    # ---- pseudo-token dispatch along seq ------------------------------
    t = s * k
    ids = choice.reshape(b, t)                                # [B,T]
    order = jnp.argsort(ids, axis=1, stable=True)             # [B,T]
    sorted_ids = jnp.take_along_axis(ids, order, axis=1)
    counts = jnp.sum(jax.nn.one_hot(ids, e, dtype=jnp.int32), axis=1)  # [B,E]
    starts = jnp.cumsum(counts, axis=1) - counts              # [B,E]
    # rank of each sorted pseudo-token within its expert group
    rank_sorted = jnp.arange(t)[None, :] - jnp.take_along_axis(
        starts, sorted_ids, axis=1)
    # invert the sort: rank[b, order[b,i]] = rank_sorted[b,i]
    rank = jnp.zeros((b, t), jnp.int32)
    rank = jax.vmap(lambda r, o, rs: r.at[o].set(rs))(rank, order, rank_sorted)

    # ---- gather tokens into [B, E, cap, D] -----------------------------
    slot_i = starts[:, :, None] + jnp.arange(cap)[None, None, :]   # [B,E,cap]
    valid = jnp.arange(cap)[None, None, :] < counts[:, :, None]
    slot_i = jnp.clip(slot_i, 0, t - 1)
    slot_tok = jnp.take_along_axis(order, slot_i.reshape(b, -1), axis=1)
    src_tok = jnp.clip(slot_tok // k, 0, s - 1)                    # [B,E*cap]
    xe = jnp.take_along_axis(x, src_tok[..., None], axis=1)
    xe = xe.reshape(b, e, cap, d)
    xe = jnp.where(valid[..., None], xe, 0.0)
    xe = shard(xe, "batch", "expert", "capacity", "embed")

    # ---- expert FFN (expert axis model-sharded) ------------------------
    h = jnp.einsum("becd,edf->becf", xe, p["w_gate"])
    h = jax.nn.silu(h) * jnp.einsum("becd,edf->becf", xe, p["w_up"])
    h = shard(h, "batch", "expert", "capacity", "mlp")
    ye = jnp.einsum("becf,efd->becd", h, p["w_down"])
    ye = shard(ye, "batch", "expert", "capacity", "embed")

    # ---- combine: token-side gather from [B, E*cap, D] ------------------
    flat = ye.reshape(b, e * cap, d)
    tok_slot = ids * cap + rank                                    # [B,T]
    in_cap = rank < cap
    tok_slot = jnp.clip(tok_slot, 0, e * cap - 1)
    yp = jnp.take_along_axis(flat, tok_slot[..., None], axis=1)    # [B,T,D]
    yp = jnp.where(in_cap[..., None], yp, 0.0).reshape(b, s, k, d)
    y = jnp.sum(yp * gates[..., None].astype(yp.dtype), axis=2)
    return shard(y.astype(x.dtype), "batch", "seq", "embed"), aux


# ---------------------------------------------------------------------------
# embeddings


def embed_defs(cfg) -> Tree:
    d = cfg.d_model
    if cfg.tie_embeddings:
        # one table for both ends, vocab-sharded: the lookup gathers across
        # shards, and the logits come out vocab-sharded
        return {"tok": ParamDef((cfg.padded_vocab, d), ("vocab", "embed"),
                                scale=1.0, fan_in=d)}
    return {
        # input table D-sharded (tiny per-device slice, gather stays local)
        "tok": ParamDef((cfg.padded_vocab, d), ("vocab_rep", "embed_shard"),
                        scale=1.0, fan_in=d),
        # unembed vocab-sharded: logits come out vocab-sharded, loss reduces
        "out": ParamDef((d, cfg.padded_vocab), ("embed", "vocab")),
    }


def embed(p: Tree, tokens: jax.Array) -> jax.Array:
    x = jnp.take(p["tok"], tokens, axis=0)
    return shard(x, "batch", "seq", "embed")


def unembed(p: Tree, x: jax.Array) -> jax.Array:
    out = GW(p["out"]) if "out" in p else GW(p["tok"]).T
    return shard(x @ out, "batch", "seq", "vocab")
