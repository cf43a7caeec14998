"""Operation and byte counts from shapes, against numbers worked out by
hand."""

import pytest

from bench.tests import util  # noqa: F401  (puts src on sys.path)
from bench.lib import flops as F

#: d=4, 2 heads of width 2 over 1 kv head, 4 experts of width 3, top-2,
#: vocabulary 10, one layer
DIMS = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
        "head_dim": 2, "intermediate_size": 3, "num_local_experts": 4,
        "num_experts_per_tok": 2, "vocab_size": 10, "num_hidden_layers": 1}


def test_layer_and_steps():
    """One token through the layer: q/k/v 2*4*(2*2 + 2*1*2) = 64, o
    2*(2*2)*4 = 32, router 2*4*4 = 32, experts 2*3*2*4*3 = 144: 272.
    One (query, key) pair: 4*2 heads*2 = 16.
    Prefill of 1 x 2 tokens: 2*272 + 3 pairs*16 = 592, logits of the last
    position 2*4*10 = 80: 672.  Decode of 1 token against 3 keys:
    272 + 48 + 80 = 400."""
    assert F.layer_dense_flops(DIMS, 1) == 272
    assert F.attention_core_flops(DIMS, 1) == 16
    assert F.prefill_flops(DIMS, 1, 2) == 672
    assert F.decode_step_flops(DIMS, 1, 3) == 400


def test_flash_decode_cost():
    """Batch 2, 1 kv head, 2 query heads of width 2, frontiers 3 and 5:
    8 keys.  FLOPs 4*1*2*2*8 = 128.  Bytes: keys and values 2*1*8*2*2 =
    64, queries and output 2*2*1*2*2*2 = 32: 96."""
    assert F.flash_decode_cost(2, 1, 2, 2, [3, 5]) == (128, 96)


def test_roofline():
    """1e12 FLOPs at 197e12 FLOP/s is 5.08 ms; 8.19e9 bytes at 819e9 B/s
    is 10 ms: memory sets the bound."""
    t, bound = F.roofline_seconds(1e12, 8.19e9, 197e12, 819e9)
    assert bound == "memory" and t == pytest.approx(0.01)
