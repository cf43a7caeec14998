"""Zamba2's model FLOPs per serve step, worked out from the configuration
file's published sizes (what the mathematics needs, not what the program
happens to compute), and the batch and length of a decode step recovered
from its ``flash_decode`` cost.

A matrix multiplication of [m, k] by [k, n] is 2*m*k*n operations.  Per
token, a Mamba2 layer is its projections (z, x, B/C, dt in; out), the
depthwise conv (2 * width per channel) and the recurrence: the state
update exp(dt A) S + dt x B^T (3 per state element) and the read-out
S C (2 per state element).  An invocation of a shared block is its
attention projections from the concat width, the attention core (4 *
head_dim per query and key pair per head), the gated MLP, its adapter
and its linear.  Logits are counted for the sampled position only.
"""

from __future__ import annotations

from typing import Tuple


def _z(dims: dict) -> dict:
    d = dims["hidden_size"]
    L = dims["num_hidden_layers"]
    return {"d": d, "di": dims["mamba_expand"] * d,
            "nh": dims["n_mamba_heads"], "p": dims["mamba_headdim"],
            "bc": 2 * dims["mamba_ngroups"] * dims["mamba_d_state"],
            "n": dims["mamba_d_state"], "w": dims["mamba_d_conv"], "L": L,
            "inv": sum(1 for i in dims["hybrid_layer_ids"] if i < L),
            "da": dims["attention_hidden_size"],
            "h": dims["num_attention_heads"],
            "kv": dims["num_key_value_heads"],
            "hd": dims["attention_head_dim"],
            "f": dims["intermediate_size"], "r": dims["adapter_rank"],
            "v": dims["vocab_size"]}


def mamba_layer_flops(dims: dict) -> float:
    """One Mamba2 layer, per token."""
    z = _z(dims)
    d, di, nh = z["d"], z["di"], z["nh"]
    proj = 2 * d * (2 * di + z["bc"] + nh) + 2 * di * d
    conv = 2 * z["w"] * (di + z["bc"])
    scan = 5 * nh * z["p"] * z["n"]
    return float(proj + conv + scan)


def shared_block_flops(dims: dict) -> float:
    """One invocation of a shared block, per token, without the attention
    core."""
    z = _z(dims)
    d, f, hd = z["d"], z["f"], z["hd"]
    attn = 2 * z["da"] * (z["h"] + 2 * z["kv"]) * hd + 2 * z["h"] * hd * d
    mlp = 2 * 3 * d * f
    adapter = 2 * z["r"] * (d + 2 * f)
    return float(attn + mlp + adapter + 2 * d * d)


def attention_core_flops(dims: dict, key_pairs: int) -> float:
    z = _z(dims)
    return float(4 * z["h"] * z["hd"] * key_pairs)


def prefill_flops(dims: dict, batch: int, seq: int) -> float:
    """One prefill of ``batch`` prompts of ``seq`` tokens (causal)."""
    z = _z(dims)
    tokens = batch * seq
    pairs = batch * seq * (seq + 1) // 2
    return (z["L"] * mamba_layer_flops(dims) * tokens
            + z["inv"] * (shared_block_flops(dims) * tokens
                          + attention_core_flops(dims, pairs))
            + 2.0 * batch * z["d"] * z["v"])


def decode_step_flops(dims: dict, batch: int, length: int) -> float:
    """One decode step of ``batch`` sequences whose new token attends to
    ``length`` keys (itself included)."""
    z = _z(dims)
    return (z["L"] * mamba_layer_flops(dims) * batch
            + z["inv"] * (shared_block_flops(dims) * batch
                          + attention_core_flops(dims, batch * length))
            + 2.0 * batch * z["d"] * z["v"])


def batch_and_length(dims: dict, kernel) -> Tuple[int, int]:
    """(batch, keys per sequence) of a decode step from its flash_decode
    (FLOPs, bytes) (``flops.flash_decode_cost`` with one length for the
    whole batch): FLOPs = 4 kv g hd B L, bytes = FLOPs / g + 4 B kv g hd."""
    flops, nbytes = kernel
    kv, hd = dims["num_key_value_heads"], dims["head_dim"]
    g = dims["num_attention_heads"] // kv
    b = (nbytes - flops / g) / (4.0 * kv * g * hd)
    length = flops / (4.0 * kv * g * hd * b)
    if abs(b - round(b)) > 1e-6 or abs(length - round(length)) > 1e-6:
        raise ValueError(f"no whole batch and length give {kernel}")
    return int(round(b)), int(round(length))


def step_flops(dims: dict, work: list, i: int):
    """Model FLOPs of ``work[i]`` (the serve loop's per-step records, in
    order): a decode step by its kernel entry; a prefill (no kernel) by
    the decode step after it, whose length is the prompt's plus one.
    ``None`` where neither tells."""
    if work[i].get("kernel"):
        return decode_step_flops(dims, *batch_and_length(dims,
                                                         work[i]["kernel"]))
    nxt = work[i + 1] if i + 1 < len(work) else None
    if nxt and nxt.get("kernel"):
        b, length = batch_and_length(dims, nxt["kernel"])
        return prefill_flops(dims, b, length - 1)
    return None
