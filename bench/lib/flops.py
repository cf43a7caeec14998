"""Operations and bytes worked out from shapes: granite's prefill and
decode steps (model FLOPs: what the mathematics needs, not what the
program happens to compute) and the ``flash_decode`` kernel.

``dims`` is a configuration file's dictionary (Hugging Face key names).
A matrix multiplication of [m, k] by [k, n] is 2*m*k*n operations.
"""

from __future__ import annotations

from typing import Sequence


def _d(dims: dict):
    d = dims["hidden_size"]
    h, kv = dims["num_attention_heads"], dims["num_key_value_heads"]
    hd = dims["head_dim"]
    return (d, h, kv, hd, dims["intermediate_size"], dims["num_local_experts"],
            dims["num_experts_per_tok"], dims["vocab_size"],
            dims["num_hidden_layers"])


def layer_dense_flops(dims: dict, tokens: int) -> float:
    """Per layer: q/k/v/o projections, the router, and the ``k`` experts a
    token is routed to (gate, up and down matrices), for ``tokens`` tokens."""
    d, h, kv, hd, f, e, k, _, _ = _d(dims)
    proj = 2 * tokens * d * (h * hd + 2 * kv * hd) + 2 * tokens * h * hd * d
    router = 2 * tokens * d * e
    experts = 2 * 3 * tokens * k * d * f
    return float(proj + router + experts)


def attention_core_flops(dims: dict, key_pairs: int) -> float:
    """Scores and the weighted sum of values over ``key_pairs`` (query,
    key) pairs per head: 2*hd each for q.k and for p.v."""
    _, h, _, hd, _, _, _, _, _ = _d(dims)
    return float(4 * h * hd * key_pairs)


def prefill_flops(dims: dict, batch: int, seq: int) -> float:
    """One prefill of ``batch`` prompts of ``seq`` tokens, causal (query i
    sees keys 0..i), with logits for the last position only."""
    d, _, _, _, _, _, _, v, layers = _d(dims)
    pairs = batch * seq * (seq + 1) // 2
    per_layer = layer_dense_flops(dims, batch * seq) + attention_core_flops(
        dims, pairs)
    return layers * per_layer + 2.0 * batch * d * v


def decode_step_flops(dims: dict, batch: int, length: int) -> float:
    """One decode step of ``batch`` sequences whose new token attends to
    ``length`` keys (itself included)."""
    d, _, _, _, _, _, _, v, layers = _d(dims)
    per_layer = layer_dense_flops(dims, batch) + attention_core_flops(
        dims, batch * length)
    return layers * per_layer + 2.0 * batch * d * v


def flash_decode_cost(batch: int, kv: int, g: int, hd: int,
                      lengths: Sequence[int], itemsize: int = 2):
    """(FLOPs, bytes) one ``flash_decode`` call needs: per sequence b and
    kv head, G query heads against lengths[b] cached keys and values.  The
    bytes are the cache rows below each frontier (keys and values), the
    queries and the output."""
    keys = sum(int(n) for n in lengths)
    assert len(lengths) == batch
    flops = 4.0 * kv * g * hd * keys
    cache = 2.0 * kv * keys * hd * itemsize
    q_out = 2.0 * batch * kv * g * hd * itemsize
    return flops, cache + q_out


def roofline_seconds(flops: float, nbytes: float, peak_flops: float,
                     peak_bw: float):
    """(least seconds, which bound sets it)."""
    t_c, t_m = flops / peak_flops, nbytes / peak_bw
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
