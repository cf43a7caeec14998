"""Plain float32 forward pass of Zamba2-7B, and the weights the benchmark
makes for it.

The weights are made here, from the seed, in one jitted call on the
device, in the tree layout and types the program's serve path takes
(``make_weights``).  The reference reads the same arrays, as float32, and
imports nothing of the program.  Its sizes come from the configuration
file's published keys (``hidden_size``, ``n_mamba_heads``, ...).

What the reference computes, per sequence (teacher-forced), following
the published Zamba2 description and its Hugging Face modelling code:

- x0 = token embedding (the table is tied to the output);
- for each layer l of ``num_hidden_layers``: if l is the n-th entry of
  ``hybrid_layer_ids``, shared block n % ``num_mem_blocks`` runs on
  concat(x, x0): RMSNorm, causal multi-head attention (rotary embedding
  over the whole head, rotate-half, base ``rope_theta``; softmax scale
  (head_dim / 2) ** -0.5), ``o_proj`` to ``hidden_size``, RMSNorm, then a
  gated MLP gelu(x Wg + (x A_n) G_n) * (x Wu + (x A_n) U_n) Wd with the
  invocation's own rank-``adapter_rank`` adapter, no residual; its output
  through the invocation's own linear is t, else t = 0;
- the Mamba2 layer: x + mixer(RMSNorm(x + t)) (the residual is taken
  before t is added).  The mixer: z, x, B/C and dt projections; a causal
  depthwise conv of width ``mamba_d_conv`` with bias, then silu, on x and
  on B/C; dt = softplus(dt + dt_bias); per head h of ``n_mamba_heads``
  (head h in group h // (heads / ``mamba_ngroups``)), the stepwise
  recurrence S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t +
  D x_t; the gated RMSNorm over each group, gate first:
  norm(y * silu(z)) * scale; the out projection;
- final RMSNorm, logits against the tied table, over the real vocabulary.

Every matrix product runs at ``precision=HIGHEST``.  ``quant="fp8"``
computes each matrix product with both operands rounded to float8_e4m3fn
under a per-tensor scale: the control, one precision step below the
configured bfloat16.  ``quant="bf16"`` is bfloat16 inference: every
tensor passed between operations (each product's operands and result,
norm outputs, the conv's, the residual stream) rounded to bfloat16, the
recurrent state, dt and the sums inside norms, softmax and attention
scores kept in float32: a witness of how far bfloat16 alone moves the
greedy choice.
"""

from __future__ import annotations

import functools
import math
import sys
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


def sizes(dims: dict) -> dict:
    """The sizes the reference and the weights use, by short name."""
    d = dims["hidden_size"]
    return {"d": d, "di": dims["mamba_expand"] * d,
            "nh": dims["n_mamba_heads"], "p": dims["mamba_headdim"],
            "g": dims["mamba_ngroups"], "n": dims["mamba_d_state"],
            "w": dims["mamba_d_conv"], "L": dims["num_hidden_layers"],
            "ids": [i for i in dims["hybrid_layer_ids"]
                    if i < dims["num_hidden_layers"]],
            "nb": dims["num_mem_blocks"], "da": dims["attention_hidden_size"],
            "h": dims["num_attention_heads"],
            "kv": dims["num_key_value_heads"],
            "hd": dims["attention_head_dim"],
            "f": dims["intermediate_size"], "r": dims["adapter_rank"],
            "vp": padded_vocab(dims["vocab_size"])}


# ---------------------------------------------------------------------------
# weights


def weight_specs(dims: dict) -> Dict[str, tuple]:
    """Leaf path -> (shape, dtype, kind, std): the serve path's tree."""
    z = sizes(dims)
    d, di, nh, L = z["d"], z["di"], z["nh"], z["L"]
    bc, w, f, r = 2 * z["g"] * z["n"], z["w"], z["f"], z["r"]
    q, kv = z["h"] * z["hd"], z["kv"] * z["hd"]
    n_inv = len(z["ids"])
    bf = jnp.bfloat16
    out_std = 1.0 / math.sqrt(2 * L)
    specs = {
        "embed/tok": ((z["vp"], d), bf, "normal", 1.0 / math.sqrt(d)),
        "final_norm/scale": ((d,), bf, "scale", 0.1),
        "blocks/ln/scale": ((L, d), bf, "scale", 0.1),
        "blocks/w_z": ((L, d, di), bf, "normal", d ** -0.5),
        "blocks/w_x": ((L, d, di), bf, "normal", d ** -0.5),
        "blocks/w_bc": ((L, d, bc), bf, "normal", d ** -0.5),
        "blocks/w_dt": ((L, d, nh), bf, "normal", d ** -0.5),
        "blocks/conv_x": ((L, w, di), bf, "normal", w ** -0.5),
        "blocks/conv_x_b": ((L, di), bf, "normal", 0.1),
        "blocks/conv_bc": ((L, w, bc), bf, "normal", w ** -0.5),
        "blocks/conv_bc_b": ((L, bc), bf, "normal", 0.1),
        "blocks/a_log": ((L, nh), bf, "a_log", 0.0),
        "blocks/dt_bias": ((L, nh), bf, "dt_bias", 0.0),
        "blocks/d_skip": ((L, nh), bf, "scale", 0.1),
        "blocks/norm/scale": ((L, di), bf, "scale", 0.1),
        "blocks/w_out": ((L, di, d), bf, "normal", out_std / math.sqrt(di)),
        "hybrid/adapter/a": ((n_inv, d, r), bf, "normal", d ** -0.5),
        "hybrid/adapter/gate": ((n_inv, r, f), bf, "normal", 0.5 * r ** -0.5),
        "hybrid/adapter/up": ((n_inv, r, f), bf, "normal", 0.5 * r ** -0.5),
        "hybrid/linear": ((n_inv, d, d), bf, "normal", d ** -0.5),
    }
    for k in range(z["nb"]):
        b = f"shared/block{k}/"
        specs.update({
            b + "ln1/scale": ((z["da"],), bf, "scale", 0.1),
            b + "attn/wq": ((z["da"], q), bf, "normal", z["da"] ** -0.5),
            b + "attn/wk": ((z["da"], kv), bf, "normal", z["da"] ** -0.5),
            b + "attn/wv": ((z["da"], kv), bf, "normal", z["da"] ** -0.5),
            b + "attn/wo": ((q, d), bf, "normal", q ** -0.5),
            b + "ln2/scale": ((d,), bf, "scale", 0.1),
            b + "ffn/w_gate": ((d, f), bf, "normal", d ** -0.5),
            b + "ffn/w_up": ((d, f), bf, "normal", d ** -0.5),
            b + "ffn/w_down": ((f, d), bf, "normal", out_std / math.sqrt(f)),
        })
    return specs


def _nest(flat: Dict[str, object]) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any whole number (seeds may pass 2**31)."""
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.PRNGKey(word)


def _leaf(key, shape, kind, std, dims):
    """One leaf in float32.  ``a_log`` and ``dt_bias`` follow Mamba2's
    initialisation: A uniform in [1, 16]; dt log-uniform in
    [time_step_min, time_step_max], floored at time_step_floor, and
    dt_bias its inverse softplus."""
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if kind == "dt_bias":
        lo, hi = math.log(dims["time_step_min"]), math.log(dims["time_step_max"])
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        dt = jnp.maximum(dt, dims["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))
    z = jax.random.normal(key, shape, jnp.float32)
    return 1.0 + std * z if kind == "scale" else std * z


def make_weights(dims: dict, seed: int, out_shardings=None):
    """All weights, from ``seed``, in one jitted call on the device."""
    specs = weight_specs(dims)

    def build(key):
        flat = {path: _leaf(jax.random.fold_in(key, i), shape, kind, std,
                            dims).astype(dtype)
                for i, (path, (shape, dtype, kind, std)) in enumerate(
                    sorted(specs.items()))}
        return _nest(flat)

    return jax.jit(build, out_shardings=out_shardings)(key_for(seed))


# ---------------------------------------------------------------------------
# the reference


def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _r(x, quant):
    """A tensor as bfloat16 inference holds it between operations."""
    return x.astype(jnp.bfloat16).astype(jnp.float32) if quant == "bf16" \
        else x


def _q(x, quant):
    return _fp8(x) if quant == "fp8" else _r(x, quant)


def _einsum(spec, a, b, quant, round_out=True):
    out = jnp.einsum(spec, _q(a, quant), _q(b, quant), precision=HIGHEST)
    return _r(out, quant) if round_out else out


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """Rotate-half rotary embedding over the whole head.  x: [S, H, hd]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs           # [S, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _causal_conv(x, w, b):
    """Depthwise causal conv along time with bias, then silu.  x: [S, C];
    w: [W, C]."""
    k = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    out = sum(xp[i:i + x.shape[0]] * w[i] for i in range(k))
    return jax.nn.silu(out + b)


def ssm_scan(x, dt, a, B, C):
    """The stepwise Mamba2 recurrence of one sequence.  x: [S, nh, p];
    dt: [S, nh]; a: [nh] (A, negative); B, C: [S, nh, n] (each head's
    group's).  S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t.
    Returns y [S, nh, p]."""
    def step(state, inp):
        xt, dtt, bt, ct = inp
        state = state * jnp.exp(dtt * a)[:, None, None] + \
            (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return state, jnp.sum(state * ct[:, None, :], -1)

    nh, p = x.shape[1:]
    s0 = jnp.zeros((nh, p, B.shape[-1]), jnp.float32)
    return jax.lax.scan(step, s0, (x, dt, B, C))[1]


@functools.partial(jax.jit, static_argnames=("dims_t", "quant"))
def _mamba(x, extra, lw, *, dims_t, quant):
    dims = dict(dims_t)
    z = sizes({**dims, "hybrid_layer_ids": ()})
    s = x.shape[0]
    nh, p, g, n = z["nh"], z["p"], z["g"], z["n"]
    eps = dims["rms_norm_eps"]
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    h = _r(_rms(_r(x + extra, quant), lw["ln"], eps), quant)
    gate = _einsum("sd,dn->sn", h, lw["w_z"], quant)
    xin = _r(_causal_conv(_einsum("sd,dn->sn", h, lw["w_x"], quant),
                          lw["conv_x"], lw["conv_x_b"]), quant)
    bc = _r(_causal_conv(_einsum("sd,dn->sn", h, lw["w_bc"], quant),
                         lw["conv_bc"], lw["conv_bc_b"]), quant)
    dt = jax.nn.softplus(_einsum("sd,dn->sn", h, lw["w_dt"], quant)
                         + lw["dt_bias"])                     # [S, nh]
    group = jnp.arange(nh) // (nh // g)
    B = bc[:, :g * n].reshape(s, g, n)[:, group]              # [S, nh, n]
    C = bc[:, g * n:].reshape(s, g, n)[:, group]
    xh = xin.reshape(s, nh, p)
    y = ssm_scan(xh, dt, -jnp.exp(lw["a_log"]), B, C)
    y = y + lw["d_skip"][:, None] * xh
    y = y * jax.nn.silu(gate.reshape(s, nh, p))
    yg = y.reshape(s, g, nh // g * p)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
    y = yg.reshape(s, nh * p) * lw["norm"]
    return _r(x + _einsum("sn,nd->sd", y, lw["w_out"], quant), quant)


@functools.partial(jax.jit, static_argnames=("dims_t", "quant"))
def _shared(x, x0, bw, own, *, dims_t, quant):
    dims = dict(dims_t)
    s = x.shape[0]
    h, kv, hd = (dims["num_attention_heads"], dims["num_key_value_heads"],
                 dims["attention_head_dim"])
    eps, theta = dims["rms_norm_eps"], dims["rope_theta"]
    bw, own = jax.tree.map(lambda a: a.astype(jnp.float32), (bw, own))
    pos = jnp.arange(s)

    a = _r(_rms(jnp.concatenate([x, x0], -1), bw["ln1"], eps), quant)
    q = _r(_rope(_einsum("sd,dn->sn", a, bw["wq"], quant).reshape(s, h, hd),
                 pos, theta), quant).reshape(s, kv, h // kv, hd)
    k = _r(_rope(_einsum("sd,dn->sn", a, bw["wk"], quant).reshape(s, kv, hd),
                 pos, theta), quant)
    v = _einsum("sd,dn->sn", a, bw["wv"], quant).reshape(s, kv, hd)
    sc = _einsum("skgd,tkd->kgst", q, k, quant, round_out=False) * \
        (hd / 2) ** -0.5
    sc = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :],
                   sc, -jnp.inf)
    o = _einsum("kgst,tkd->skgd", jax.nn.softmax(sc, -1), v,
                quant).reshape(s, h * hd)
    a = _r(_rms(_einsum("sn,nd->sd", o, bw["wo"], quant), bw["ln2"], eps),
           quant)
    low = _einsum("sd,dr->sr", a, own["a"], quant)
    g = _r(_einsum("sd,df->sf", a, bw["w_gate"], quant)
           + _einsum("sr,rf->sf", low, own["gate"], quant), quant)
    u = _r(_einsum("sd,df->sf", a, bw["w_up"], quant)
           + _einsum("sr,rf->sf", low, own["up"], quant), quant)
    m = _einsum("sf,fd->sd", _r(jax.nn.gelu(g, approximate=False), quant) * u,
                bw["w_down"], quant)
    return _einsum("sd,de->se", m, own["linear"], quant)


@functools.partial(jax.jit, static_argnames=("dims_t", "quant"))
def _head(x, scale, tok, *, dims_t, quant):
    dims = dict(dims_t)
    x = _r(_rms(x, scale.astype(jnp.float32), dims["rms_norm_eps"]), quant)
    logits = _einsum("sd,vd->sv", x, tok.astype(jnp.float32), quant)
    return logits[:, :dims["vocab_size"]]


def _static(dims: dict) -> tuple:
    """The configuration's numbers (lists as tuples), hashable for jit."""
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in dims.items()
        if (isinstance(v, (int, float)) and not isinstance(v, bool))
        or (isinstance(v, list) and all(isinstance(i, int) for i in v))))


def logits(weights: dict, dims: dict, tokens, quant: Optional[str] = None):
    """Float32 logits [S, vocab] at every position of one sequence
    ``tokens`` [S]."""
    dims_t = _static(dims)
    z = sizes(dims)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.take(weights["embed"]["tok"], tokens, axis=0).astype(jnp.float32)
    x0 = x
    blocks, own_all = weights["blocks"], weights["hybrid"]
    for layer in range(z["L"]):
        extra = jnp.zeros_like(x)
        if layer in z["ids"]:
            n = z["ids"].index(layer)
            bw = weights["shared"][f"block{n % z['nb']}"]
            bw = {"ln1": bw["ln1"]["scale"], "ln2": bw["ln2"]["scale"],
                  **bw["attn"], **bw["ffn"]}
            own = {**{k: v[n] for k, v in own_all["adapter"].items()},
                   "linear": own_all["linear"][n]}
            extra = _shared(x, x0, bw, own, dims_t=dims_t, quant=quant)
        lw = {k: (v["scale"] if isinstance(v, dict) else v)[layer]
              for k, v in blocks.items()}
        x = _mamba(x, extra, lw, dims_t=dims_t, quant=quant)
    return _head(x, weights["final_norm"]["scale"], weights["embed"]["tok"],
                 dims_t=dims_t, quant=quant)


def served_gaps(weights: dict, dims: dict, prompt, served,
                control: bool = False) -> dict:
    """How far below the reference's best logit each served token lies.

    ``prompt`` [P] and ``served`` [G] are one request's prompt and the
    tokens the program served for it.  The reference runs once over
    prompt + served[:-1]; position P-1+i predicts served[i].  Returns the
    gaps [G] (0 where the served token is the reference's argmax) and,
    with ``control``, the gaps of the tokens the fp8 control puts first
    at the same positions (``control_gaps``), and those of the tokens the
    reference in bfloat16 puts first (``bf16_gaps``, whose statistics go
    to stderr: the reading a sound bfloat16 program is expected near).
    """
    p = len(prompt)
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)[:-1]])
    ref = logits(weights, dims, seq)[p - 1:]
    best = jnp.max(ref, -1)

    def gaps(tokens):
        return np.asarray(
            best - jnp.take_along_axis(ref, tokens[:, None], -1)[:, 0])

    out = {"gaps": gaps(jnp.asarray(served))}
    if control:
        for key, quant in (("control_gaps", "fp8"), ("bf16_gaps", "bf16")):
            low = logits(weights, dims, seq, quant=quant)[p - 1:]
            out[key] = gaps(jnp.argmax(low, -1))
        g = out["bf16_gaps"]
        print(f"bf16 reference in the program's place: share_gap_over_0.1 "
              f"{float(np.mean(g > 0.1))}, off_argmax_share "
              f"{float(np.mean(g > 0))}, max_logit_gap {float(np.max(g))}",
              file=sys.stderr)
    return out
