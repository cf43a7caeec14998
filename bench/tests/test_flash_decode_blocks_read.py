"""flash_decode_blocks_read on synthetic work lists: one granite decode
step and one zamba2 decode step (a chip's 8 KV heads), each at a
frontier inside the cache; the cache's length comes from the cell's
traffic, the block size from the program's decode_plan."""

import os

import pytest

from bench.tests import util  # noqa: F401  (puts src on sys.path)
from bench.lib import flops as F
from bench.lib import harness as H
from repro.kernels.flash_decode.flash_decode import decode_plan


def _records(config, batch, frontier, tp=1):
    dims = H.load_json(os.path.join(H.BENCH, "configs", config + ".json"))
    kv, h = dims["num_key_value_heads"], dims["num_attention_heads"]
    kern = F.flash_decode_cost(batch, kv, h // kv, dims["head_dim"],
                               [frontier] * batch)
    work = [{"t": 0.5, "kernel": None, "flops": 0.0},      # a prefill
            {"t": 1.0, "kernel": kern, "flops": 0.0},
            {"t": 9.0, "kernel": kern, "flops": 0.0}]      # after the trace
    assert dims.get("tensor_parallel", 1) == tp
    return {"work": work, "tracer": (0.0, 2.0), "dims": dims}


@pytest.mark.parametrize("config,batch,frontier,t,kv,g,hd,tp", [
    ("granite-moe-1b-a400m", 48, 640, 1536, 8, 2, 64, 1),
    ("zamba2-7b", 32, 1000, 2048, 8, 1, 224, 4)])
def test_blocks_read_of_one_step(config, batch, frontier, t, kv, g, hd, tp):
    bk, steps = decode_plan(kv, g, hd, t, 2)
    got = H.read_per_layer("flash_decode_blocks_read",
                           _records(config, batch, frontier, tp))
    assert got == pytest.approx(100.0 * -(-frontier // bk) / steps)
    assert 0.0 < got < 100.0


def test_granite_step_reads_two_of_three_blocks():
    """granite's 8 x 64 heads over a 1536-long cache: bk 512, so a frontier
    of 640 reads 2 of the row's 3 blocks."""
    got = H.read_per_layer("flash_decode_blocks_read",
                           _records("granite-moe-1b-a400m", 48, 640))
    assert got == pytest.approx(200.0 / 3)


def test_no_matching_shape_or_no_kernel_reads_none():
    rec = _records("granite-moe-1b-a400m", 5, 640)       # no batch of 5
    assert H.read_per_layer("flash_decode_blocks_read", rec) is None
    rec["work"] = [w for w in rec["work"] if not w["kernel"]]
    assert H.read_per_layer("flash_decode_blocks_read", rec) is None
