"""Sub-quadratic sequence mixers: RWKV6 (Finch) and Mamba2 (SSD).

Both use the same chunked-recurrence strategy: the sequence is split into
chunks of ``cfg.chunk_size``; a lax.scan carries the recurrent state across
chunks while each chunk computes intra-chunk interactions with a masked
pairwise-decay tensor.  All pairwise exponents are of the form
``logA[t-1] - logA[i]`` with i <= t-1 and logA non-increasing, so every
``exp`` argument is <= 0 — numerically safe without secondary chunking.

State shapes (per layer, carried through decode):
  RWKV6  : [B, nh, hd, hd]   (key-dim x value-dim outer-product state)
  Mamba2 : [B, nh, hd, st]   (head-dim x ssm-state outer-product state;
           kept flat as [B, nh, hd*st] in the serve cache)
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from .layers import rms_norm
from .params import ParamDef

Tree = Dict[str, Any]

LORA_MAA = 32        # rwkv6 token-shift lora rank
LORA_DECAY = 64      # rwkv6 data-dependent decay lora rank


# ===========================================================================
# RWKV6 (Finch) — data-dependent decay linear attention
# ===========================================================================


def rwkv_defs(cfg, layers: int) -> Tree:
    d = cfg.d_model
    nh = d // cfg.ssm_head_dim
    hd = cfg.ssm_head_dim
    f = cfg.d_ff
    L = (layers,)
    ax = ("layers",)

    def w(shape, axes, **kw):
        return ParamDef(L + shape, ax + axes, **kw)

    return {
        "ln1": {"scale": w((d,), ("embed",), init="ones")},
        "ln2": {"scale": w((d,), ("embed",), init="ones")},
        # token-shift ddlerp
        "maa_x": w((d,), ("embed",), init="zeros"),
        "maa_rkvwg": w((5, d), (None, "embed"), init="zeros"),
        "maa_w1": w((d, 5 * LORA_MAA), ("embed", "lora")),
        "maa_w2": w((5, LORA_MAA, d), (None, "lora", "embed"), fan_in=LORA_MAA),
        # data-dependent decay
        "decay": w((d,), ("embed",), init="const", scale=-6.0),
        "td_w1": w((d, LORA_DECAY), ("embed", "lora")),
        "td_w2": w((LORA_DECAY, d), ("lora", "embed"), fan_in=LORA_DECAY),
        "bonus": w((nh, hd), ("ssm_heads", None)),     # time_faaaa / u
        # projections
        "wr": w((d, d), ("embed", "ssm_inner")),
        "wk": w((d, d), ("embed", "ssm_inner")),
        "wv": w((d, d), ("embed", "ssm_inner")),
        "wg": w((d, d), ("embed", "ssm_inner")),
        "wo": w((d, d), ("ssm_inner", "embed"),
                scale=1.0 / max(1, 2 * cfg.num_layers) ** 0.5),
        "ln_x": {"scale": w((d,), ("embed",), init="ones")},
        # channel mix
        "cm_maa_k": w((d,), ("embed",), init="zeros"),
        "cm_maa_r": w((d,), ("embed",), init="zeros"),
        "cm_wk": w((d, f), ("embed", "mlp")),
        "cm_wv": w((f, d), ("mlp", "embed"),
                   scale=1.0 / max(1, 2 * cfg.num_layers) ** 0.5),
        "cm_wr": w((d, d), ("embed", "ssm_inner")),
    }


def _token_shift(x: jax.Array, prev: jax.Array) -> jax.Array:
    """x[t-1] stream: prev is the last token of the previous segment."""
    return jnp.concatenate([prev[:, None, :], x[:, :-1, :]], axis=1)


def rwkv_wkv_chunked(r, k, v, w_log, u, state, chunk: int):
    """WKV recurrence, chunked.

    r/k/v/w_log: [B, T, nh, hd]; u: [nh, hd]; state: [B, nh, hd, hd].
    out_t = r_t . (S_t + u*k_t (x) v_t);  S_{t+1} = diag(w_t) S_t + k_t (x) v_t
    Returns out [B, T, nh, hd], final state.
    """
    b, t, nh, hd = r.shape
    c = min(chunk, t)
    tp = -(-t // c) * c
    if tp != t:
        # identity padding: k=v=r=0 contribute nothing, w_log=0 is decay 1
        pad = ((0, 0), (0, tp - t), (0, 0), (0, 0))
        r, k, v = (jnp.pad(a, pad) for a in (r, k, v))
        w_log = jnp.pad(w_log, pad)
    nchunks = tp // c

    def split(x):
        return jnp.moveaxis(x.reshape(b, nchunks, c, nh, hd), 1, 0)

    rc, kc, vc, wc = split(r), split(k), split(v), split(w_log)

    def step(state, xs):
        rr, kk, vv, ww = (x.astype(jnp.float32) for x in xs)  # [B,c,nh,hd]
        logA = jnp.cumsum(ww, axis=1)                 # inclusive
        logA_prev = logA - ww                         # exclusive
        # inter-chunk: state contribution
        q_in = rr * jnp.exp(logA_prev)
        inter = jnp.einsum("bcnd,bnde->bcne", q_in, state)
        # intra-chunk pairwise (strictly lower-triangular)
        diff = logA_prev[:, :, None] - logA[:, None, :, :, :]  # [B,c,c,nh,hd]
        mask = (jnp.arange(c)[:, None] > jnp.arange(c)[None, :])
        dec = jnp.exp(jnp.minimum(diff, 0.0)) * mask[None, :, :, None, None]
        scores = jnp.einsum("btnd,bind,btind->btin", rr, kk, dec)
        intra = jnp.einsum("btin,bine->btne", scores, vv)
        # bonus (current token)
        bonus = jnp.einsum("btnd,nd,btnd->btn", rr, u.astype(jnp.float32), kk)
        intra = intra + bonus[..., None] * vv
        # state update
        k_dec = kk * jnp.exp(logA[:, -1:, :, :] - logA)
        new_state = state * jnp.exp(logA[:, -1])[..., None] + \
            jnp.einsum("bind,bine->bnde", k_dec, vv)
        return new_state, (inter + intra).astype(r.dtype)

    state, out = jax.lax.scan(step, state.astype(jnp.float32),
                              (rc, kc, vc, wc))
    return jnp.moveaxis(out, 0, 1).reshape(b, tp, nh, hd)[:, :t], state


def rwkv_block(p: Tree, x: jax.Array, cfg, state: Optional[Tree] = None
               ) -> Tuple[jax.Array, Optional[Tree]]:
    """One RWKV6 layer (time mix + channel mix).  state carries
    {"wkv": [B,nh,hd,hd], "shift_tm": [B,D], "shift_cm": [B,D]} for decode;
    None in training mode (shift uses zeros before t=0)."""
    b, t, d = x.shape
    nh, hd = d // cfg.ssm_head_dim, cfg.ssm_head_dim
    eps = cfg.norm_eps
    decode = state is not None

    # ---- time mix -------------------------------------------------------
    xn = rms_norm(x, p["ln1"]["scale"], eps)
    prev_tm = state["shift_tm"] if decode else jnp.zeros((b, d), x.dtype)
    xprev = _token_shift(xn, prev_tm)
    dx = xprev - xn
    xxx = xn + dx * p["maa_x"]
    ddd = jnp.tanh(xxx @ p["maa_w1"]).reshape(b, t, 5, LORA_MAA)
    ddd = jnp.einsum("btfl,fld->btfd", ddd, p["maa_w2"])
    mixed = xn[:, :, None, :] + dx[:, :, None, :] * \
        (p["maa_rkvwg"][None, None] + ddd)
    xr, xk, xv, xw, xg = (mixed[:, :, i] for i in range(5))

    r = (xr @ p["wr"]).reshape(b, t, nh, hd)
    k = (xk @ p["wk"]).reshape(b, t, nh, hd)
    v = (xv @ p["wv"]).reshape(b, t, nh, hd)
    g = jax.nn.silu(xg @ p["wg"])
    r = shard(r, "batch", "seq", "ssm_heads", None)
    k = shard(k, "batch", "seq", "ssm_heads", None)
    v = shard(v, "batch", "seq", "ssm_heads", None)

    dd = p["decay"] + jnp.tanh(xw @ p["td_w1"]) @ p["td_w2"]
    w_log = -jnp.exp(dd.astype(jnp.float32))             # log decay, < 0
    w_log = w_log.reshape(b, t, nh, hd)

    wkv0 = state["wkv"] if decode else \
        jnp.zeros((b, nh, hd, hd), jnp.float32)
    out, wkv = rwkv_wkv_chunked(r, k, v, w_log, p["bonus"], wkv0,
                                min(cfg.chunk_size, t))
    out = out.reshape(b, t, d)
    out = rms_norm(out, p["ln_x"]["scale"], eps) * g
    x = x + out @ p["wo"]
    x = shard(x, "batch", "seq", "embed")

    # ---- channel mix ----------------------------------------------------
    xn2 = rms_norm(x, p["ln2"]["scale"], eps)
    prev_cm = state["shift_cm"] if decode else jnp.zeros((b, d), x.dtype)
    xprev2 = _token_shift(xn2, prev_cm)
    dx2 = xprev2 - xn2
    xk2 = xn2 + dx2 * p["cm_maa_k"]
    xr2 = xn2 + dx2 * p["cm_maa_r"]
    kk = jnp.square(jax.nn.relu(xk2 @ p["cm_wk"]))
    kk = shard(kk, "batch", "seq", "mlp")
    cm = jax.nn.sigmoid(xr2 @ p["cm_wr"]) * (kk @ p["cm_wv"])
    x = x + cm
    x = shard(x, "batch", "seq", "embed")

    new_state = None
    if decode:
        new_state = {"wkv": wkv, "shift_tm": xn[:, -1], "shift_cm": xn2[:, -1]}
    return x, new_state


def rwkv_state_defs(cfg, batch: int, layers: int) -> Tree:
    d = cfg.d_model
    nh, hd = d // cfg.ssm_head_dim, cfg.ssm_head_dim
    return {
        "wkv": ParamDef((layers, batch, nh, hd, hd),
                        ("layers", "cache_batch", "ssm_heads", None, None),
                        dtype=jnp.float32, init="zeros"),
        "shift_tm": ParamDef((layers, batch, d),
                             ("layers", "cache_batch", "embed"), init="zeros"),
        "shift_cm": ParamDef((layers, batch, d),
                             ("layers", "cache_batch", "embed"), init="zeros"),
    }


# ===========================================================================
# Mamba2 (SSD)
# ===========================================================================


def mamba_defs(cfg, layers: int) -> Tree:
    """One Mamba2 layer per stacked row.  The in-projection is separate z,
    x, B/C and dt weights, so tensor parallelism splits heads: z and x
    over ``ssm_inner`` (whole heads of ``ssm_head_dim``), dt and the
    per-head vectors over ``ssm_heads``; B and C (``ssm_groups`` groups of
    ``ssm_state``) are replicated."""
    d, di, nh = cfg.d_model, cfg.d_inner, cfg.ssm_heads
    bc = 2 * cfg.ssm_groups * cfg.ssm_state
    wconv = cfg.ssm_conv_width
    L = (layers,)
    ax = ("layers",)

    def w(shape, axes, **kw):
        return ParamDef(L + shape, ax + axes, **kw)

    return {
        "ln": {"scale": w((d,), ("embed",), init="ones")},
        "w_z": w((d, di), ("embed", "ssm_inner")),
        "w_x": w((d, di), ("embed", "ssm_inner")),
        "w_bc": w((d, bc), ("embed", None)),          # [B | C]
        "w_dt": w((d, nh), ("embed", "ssm_heads")),
        # depthwise causal conv over x and over B/C, with a bias
        "conv_x": w((wconv, di), ("conv", "ssm_inner"), fan_in=wconv),
        "conv_x_b": w((di,), ("ssm_inner",), init="zeros"),
        "conv_bc": w((wconv, bc), ("conv", None), fan_in=wconv),
        "conv_bc_b": w((bc,), (None,), init="zeros"),
        "a_log": w((nh,), ("ssm_heads",), init="const", scale=0.5),
        "dt_bias": w((nh,), ("ssm_heads",), init="zeros"),
        "d_skip": w((nh,), ("ssm_heads",), init="ones"),
        "norm": {"scale": w((di,), ("ssm_inner",), init="ones")},
        "w_out": w((di, d), ("ssm_inner", "embed"),
                   scale=1.0 / max(1, 2 * cfg.num_layers) ** 0.5),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 buf: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv along time, then silu.  x: [B, T, C]; w: [W, C].
    buf: [B, W-1, C] history for decode (None -> zero history)."""
    wlen = w.shape[0]
    hist = buf if buf is not None else \
        jnp.zeros((x.shape[0], wlen - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([hist, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(wlen))
    return jax.nn.silu(out + b), xp[:, -(wlen - 1):, :]


def mamba_ssd_chunked(xh, B, C, logA, state, chunk: int):
    """SSD scan.  xh: [B,T,nh,hd] (dt-scaled inputs), B/C: [B,T,nh,st]
    (each head's group's), logA: [B,T,nh] (log decay <= 0), state:
    [B,nh,hd,st]."""
    b, t, nh, hd = xh.shape
    st = B.shape[-1]
    c = min(chunk, t)
    tp = -(-t // c) * c
    if tp != t:
        # identity padding: x=B=C=0 contribute nothing, logA=0 is decay 1
        pad = ((0, 0), (0, tp - t), (0, 0), (0, 0))
        xh, B, C = (jnp.pad(a, pad) for a in (xh, B, C))
        logA = jnp.pad(logA, ((0, 0), (0, tp - t), (0, 0)))
    n = tp // c

    xs = jnp.moveaxis(xh.reshape(b, n, c, nh, hd), 1, 0)
    Bs = jnp.moveaxis(B.reshape(b, n, c, nh, st), 1, 0)
    Cs = jnp.moveaxis(C.reshape(b, n, c, nh, st), 1, 0)
    As = jnp.moveaxis(logA.reshape(b, n, c, nh), 1, 0)

    def step(state, inp):
        xx, bb, cc, aa = (i.astype(jnp.float32) for i in inp)
        logA_c = jnp.cumsum(aa, axis=1)               # [B,c,nh] inclusive
        # inter: y_t += exp(logA_t) * C_t . state
        inter = jnp.einsum("btns,bnds,btn->btnd", cc, state,
                           jnp.exp(logA_c))
        # intra (i <= t): dec[t,i] = exp(logA_t - logA_i)
        diff = logA_c[:, :, None] - logA_c[:, None, :, :]   # [B,c,c,nh]
        mask = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
        dec = jnp.exp(jnp.minimum(diff, 0.0)) * mask[None, :, :, None]
        scores = jnp.einsum("btns,bins->btin", cc, bb) * dec
        intra = jnp.einsum("btin,bind->btnd", scores, xx)
        # state update
        x_dec = xx * jnp.exp(logA_c[:, -1:, :] - logA_c)[..., None]
        new_state = state * jnp.exp(logA_c[:, -1])[..., None, None] + \
            jnp.einsum("bind,bins->bnds", x_dec, bb)
        return new_state, (inter + intra)

    state, out = jax.lax.scan(step, state.astype(jnp.float32),
                              (xs, Bs, Cs, As))
    return jnp.moveaxis(out, 0, 1).reshape(b, tp, nh, hd)[:, :t], state


def gated_group_rms_norm(y: jax.Array, z: jax.Array, scale: jax.Array,
                         groups: int, eps: float, dtype) -> jax.Array:
    """Mamba2's gated RMSNorm with the gate before the norm
    (``norm_before_gate=False``): y * silu(z), normalised over each group's
    heads.  y, z: [B, T, nh, hd] -> [B, T, nh*hd] in ``dtype``, times
    ``scale``.  The sum of squares is taken per head, then summed over the
    group: with heads split over chips, a group's sum is reduced across
    the chips that hold it."""
    b, t, nh, hd = y.shape
    yg = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    ssq = jnp.sum(yg * yg, axis=-1)                           # [B,T,nh]
    var = jnp.mean(ssq.reshape(b, t, groups, nh // groups), -1) / hd
    rstd = jnp.repeat(jax.lax.rsqrt(var + eps), nh // groups, axis=-1)
    return (yg * rstd[..., None]).astype(dtype).reshape(b, t, nh * hd) * scale


@jax.named_scope("mamba")
def mamba_block(p: Tree, x: jax.Array, cfg, state: Optional[Tree] = None,
                extra: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Optional[Tree]]:
    """One Mamba2 layer with its residual: x + mixer(norm(x + extra)).
    ``extra`` (a hybrid's shared-block output) joins the layer's input but
    not its residual.  Returns the output and the state after the last
    token; ``state`` is the state before the first (None: zeros), as
    {"ssm": [B,nh,hd*st], "conv_x": [B,W-1,di], "conv_bc": [B,W-1,
    2*groups*st]}: the recurrent state flat in its last dim, which a TPU
    lays out lane-dense (a minor dim of 64 would be padded to 128)."""
    b, t, d = x.shape
    stt, nh, g = cfg.ssm_state, cfg.ssm_heads, cfg.ssm_groups
    hd = cfg.ssm_head_dim
    decode = state is not None

    xn = rms_norm(x if extra is None else x + extra, p["ln"]["scale"],
                  cfg.norm_eps)
    z = shard(xn @ p["w_z"], "batch", "seq", "ssm_inner")
    xin = shard(xn @ p["w_x"], "batch", "seq", "ssm_inner")
    dt = shard(xn @ p["w_dt"], "batch", "seq", "ssm_heads")
    xin, conv_x = _causal_conv(xin, p["conv_x"], p["conv_x_b"],
                               state["conv_x"] if decode else None)
    bc, conv_bc = _causal_conv(xn @ p["w_bc"], p["conv_bc"], p["conv_bc_b"],
                               state["conv_bc"] if decode else None)

    def per_head(a):                         # [B,T,g*st] -> [B,T,nh,st]
        a = jnp.repeat(a.reshape(b, t, g, stt), nh // g, axis=2)
        return shard(a, "batch", "seq", "ssm_heads", None)
    Bh, Ch = (per_head(a) for a in jnp.split(bc, 2, axis=-1))

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # [B,T,nh]
    logA = -jnp.exp(p["a_log"].astype(jnp.float32))[None, None] * dt
    xh = xin.reshape(b, t, nh, hd)
    xh_dt = xh.astype(jnp.float32) * dt[..., None]

    ssm0 = (state["ssm"].reshape(b, nh, hd, stt) if decode
            else jnp.zeros((b, nh, hd, stt), jnp.float32))
    with jax.named_scope("ssd"):
        y, ssm = mamba_ssd_chunked(xh_dt, Bh, Ch, logA, ssm0,
                                   min(cfg.chunk_size, t))
    y = y + p["d_skip"].astype(jnp.float32)[None, None, :, None] * \
        xh.astype(jnp.float32)
    y = gated_group_rms_norm(y, z.reshape(b, t, nh, hd), p["norm"]["scale"],
                             g, cfg.norm_eps, x.dtype)
    y = shard(y, "batch", "seq", "ssm_inner")
    out = x + y @ p["w_out"]
    out = shard(out, "batch", "seq", "embed")

    return out, {"ssm": ssm.reshape(b, nh, hd * stt), "conv_x": conv_x,
                 "conv_bc": conv_bc}


#: logical axes of the stacked Mamba2 serve state
STATE_AXES = {"ssm": ("layers", "cache_batch", "ssm_heads", None),
              "conv_x": ("layers", "cache_batch", None, "ssm_inner"),
              "conv_bc": ("layers", "cache_batch", None, None)}


def mamba_state_defs(cfg, batch: int, layers: int) -> Tree:
    di, stt, nh = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    hd = cfg.ssm_head_dim
    w = cfg.ssm_conv_width - 1
    bc = 2 * cfg.ssm_groups * stt
    shapes = {"ssm": (layers, batch, nh, hd * stt),
              "conv_x": (layers, batch, w, di),
              "conv_bc": (layers, batch, w, bc)}
    return {k: ParamDef(shapes[k], STATE_AXES[k], init="zeros",
                        dtype=jnp.float32 if k == "ssm" else jnp.bfloat16)
            for k in shapes}
