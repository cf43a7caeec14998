"""Plain float32 forward pass of granite-moe-1b-a400m as the repository
serves it, and the weights the benchmark makes for it.

The weights are made here, from the seed, in one jitted call on the
device, in the tree layout and types the program's serve path takes
(``make_weights``).  The reference reads the same arrays, as float32, and
imports nothing of the program.

What the reference computes, per sequence of ``prompt_len`` prompt tokens
followed by served tokens (teacher-forced):

- token embedding; per layer, pre-norm RMSNorm (eps, learned scale),
  grouped-query attention with rotary embeddings (rotate-half, base
  ``rope_theta``), causal, scale 1/sqrt(head_dim); residual add;
- RMSNorm, then a softmax router over ``num_local_experts``, the top
  ``num_experts_per_tok`` experts with their probabilities renormalised
  to sum to one, each expert a SwiGLU MLP (silu(x Wg) * (x Wu)) Wd;
  residual add;
- final RMSNorm and the output matrix; logits over the real vocabulary.

Expert capacity is part of the served semantics: the program groups a
prefill's routing by sequence and gives each expert
``ceil(prompt_len * k / E * capacity_factor)`` slots, first come (by
position, then by the token's choice rank), and drops the rest; a decode
step routes one token per sequence, which never overflows.  The reference
applies the same rule to the prompt positions and none to served ones.
The published model's scalar multipliers are not part of what the
program serves (see the configuration file's ``deviations``).

Every matrix product runs at ``precision=HIGHEST``.  ``quant="fp8"``
computes each product with both operands rounded to float8_e4m3fn under a
per-tensor scale: the control, one precision step below the configured
bfloat16.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


# ---------------------------------------------------------------------------
# weights


def weight_specs(dims: dict) -> Dict[str, tuple]:
    """Leaf path -> (shape, dtype, kind, std): the serve path's tree."""
    d, f = dims["hidden_size"], dims["intermediate_size"]
    h, kv, hd = (dims["num_attention_heads"], dims["num_key_value_heads"],
                 dims["head_dim"])
    e, L = dims["num_local_experts"], dims["num_hidden_layers"]
    vp = padded_vocab(dims["vocab_size"])
    bf, f32 = jnp.bfloat16, jnp.float32
    out_std = 1.0 / math.sqrt(2 * L)
    return {
        "embed/tok": ((vp, d), bf, "normal", 1.0 / math.sqrt(d)),
        "embed/out": ((d, vp), bf, "normal", 1.0 / math.sqrt(d)),
        "final_norm/scale": ((d,), bf, "scale", 0.1),
        "moe_blocks/ln1/scale": ((L, d), bf, "scale", 0.1),
        "moe_blocks/ln2/scale": ((L, d), bf, "scale", 0.1),
        "moe_blocks/attn/wq": ((L, d, h * hd), bf, "normal", d ** -0.5),
        "moe_blocks/attn/wk": ((L, d, kv * hd), bf, "normal", d ** -0.5),
        "moe_blocks/attn/wv": ((L, d, kv * hd), bf, "normal", d ** -0.5),
        "moe_blocks/attn/wo": ((L, h * hd, d), bf, "normal",
                               out_std / math.sqrt(h * hd)),
        "moe_blocks/ffn/router": ((L, d, e), f32, "normal", d ** -0.5),
        "moe_blocks/ffn/w_gate": ((L, e, d, f), bf, "normal", d ** -0.5),
        "moe_blocks/ffn/w_up": ((L, e, d, f), bf, "normal", d ** -0.5),
        "moe_blocks/ffn/w_down": ((L, e, f, d), bf, "normal",
                                  out_std / math.sqrt(f)),
    }


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
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.PRNGKey(word)


def make_weights(dims: dict, seed: int, out_shardings=None):
    """All weights, from ``seed``, in one jitted call on the device."""
    specs = weight_specs(dims)

    def build(key):
        flat = {}
        for i, (path, (shape, dtype, kind, std)) in enumerate(
                sorted(specs.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            flat[path] = (1.0 + std * z if kind == "scale"
                          else std * z).astype(dtype)
        return _nest(flat)

    return jax.jit(build, out_shardings=out_shardings)(key_for(seed))


# ---------------------------------------------------------------------------
# the reference


def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _q(x, quant):
    return _fp8(x) if quant == "fp8" else x


def _einsum(spec, a, b, quant):
    return jnp.einsum(spec, _q(a, quant), _q(b, quant), precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs           # [S, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dims_t", "prompt_len", "quant"))
def _layer(x, lw, *, dims_t, prompt_len, quant):
    dims = dict(dims_t)
    s, d = x.shape
    h, kv, hd = (dims["num_attention_heads"], dims["num_key_value_heads"],
                 dims["head_dim"])
    e, k = dims["num_local_experts"], dims["num_experts_per_tok"]
    eps, theta = dims["rms_norm_eps"], dims["rope_theta"]
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    pos = jnp.arange(s)

    a = _rms(x, lw["ln1"], eps)
    q = _einsum("sd,dn->sn", a, lw["wq"], quant).reshape(s, kv, h // kv, hd)
    kk = _einsum("sd,dn->sn", a, lw["wk"], quant).reshape(s, kv, hd)
    vv = _einsum("sd,dn->sn", a, lw["wv"], quant).reshape(s, kv, hd)
    q = _rope(q.reshape(s, h, hd), pos, theta).reshape(s, kv, h // kv, hd)
    kk = _rope(kk, pos, theta)
    sc = _einsum("skgd,tkd->kgst", q, kk, quant) / math.sqrt(hd)
    sc = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :],
                   sc, -jnp.inf)
    p = jax.nn.softmax(sc, -1)
    o = _einsum("kgst,tkd->skgd", p, vv, quant).reshape(s, h * hd)
    x = x + _einsum("sn,nd->sd", o, lw["wo"], quant)

    a = _rms(x, lw["ln2"], eps)
    probs = jax.nn.softmax(_einsum("sd,de->se", a, lw["router"], quant), -1)
    gates, choice = jax.lax.top_k(probs, k)                   # [S, k]
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    onehot = jax.nn.one_hot(choice, e, dtype=jnp.float32)     # [S, k, E]
    # capacity over the prompt: slot = earlier choices of that expert
    cap = int(math.ceil(prompt_len * k / e * dims["capacity_factor"]))
    flat = onehot[:prompt_len].reshape(prompt_len * k, e)
    rank = jnp.sum((jnp.cumsum(flat, 0) - flat) * flat, -1)
    keep = jnp.concatenate([(rank < cap).reshape(prompt_len, k),
                            jnp.ones((s - prompt_len, k), bool)])
    wts = jnp.sum(onehot * (gates * keep)[..., None], 1)       # [S, E]
    g = _einsum("sd,edf->esf", a, lw["w_gate"], quant)
    u = _einsum("sd,edf->esf", a, lw["w_up"], quant)
    y = _einsum("esf,efd->esd", jax.nn.silu(g) * u, lw["w_down"], quant)
    return x + jnp.einsum("se,esd->sd", wts, y, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("dims_t", "quant"))
def _head(x, scale, out, *, dims_t, quant):
    dims = dict(dims_t)
    x = _rms(x, scale.astype(jnp.float32), dims["rms_norm_eps"])
    logits = _einsum("sd,dv->sv", x, out.astype(jnp.float32), quant)
    return logits[:, :dims["vocab_size"]]


def logits(weights: dict, dims: dict, tokens, prompt_len: int,
           quant: Optional[str] = None):
    """Float32 logits [S, vocab] at every position of one sequence
    ``tokens`` [S] whose first ``prompt_len`` tokens are the prompt."""
    dims_t = tuple(sorted((k, v) for k, v in dims.items()
                          if isinstance(v, (int, float))
                          and not isinstance(v, bool)))
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.take(weights["embed"]["tok"], tokens, axis=0).astype(jnp.float32)
    blocks = weights["moe_blocks"]
    for layer in range(dims["num_hidden_layers"]):
        lw = {"ln1": blocks["ln1"]["scale"][layer],
              "ln2": blocks["ln2"]["scale"][layer],
              **{n: blocks["attn"][n][layer] for n in ("wq", "wk", "wv", "wo")},
              **{n: blocks["ffn"][n][layer]
                 for n in ("router", "w_gate", "w_up", "w_down")}}
        x = _layer(x, lw, dims_t=dims_t, prompt_len=prompt_len, quant=quant)
    return _head(x, weights["final_norm"]["scale"], weights["embed"]["out"],
                 dims_t=dims_t, quant=quant)


def served_gaps(weights: dict, dims: dict, prompt, served,
                control: bool = False) -> dict:
    """How far below the reference's best logit each served token lies.

    ``prompt`` [P] and ``served`` [G] are one request's prompt and the
    tokens the program served for it.  The reference runs once over
    prompt + served[:-1]; position P-1+i predicts served[i].  Returns the
    gaps [G] (0 where the served token is the reference's argmax) and,
    with ``control``, the gaps of the tokens the fp8 control puts first
    at the same positions.
    """
    p = len(prompt)
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)[:-1]])
    ref = logits(weights, dims, seq, p)[p - 1:]
    best = jnp.max(ref, -1)
    picked = jnp.take_along_axis(ref, jnp.asarray(served)[:, None], -1)[:, 0]
    out = {"gaps": np.asarray(best - picked)}
    if control:
        low = logits(weights, dims, seq, p, quant="fp8")[p - 1:]
        first = jnp.argmax(low, -1)
        out["control_gaps"] = np.asarray(
            best - jnp.take_along_axis(ref, first[:, None], -1)[:, 0])
    return out
