"""Model FLOPs of the prefill and decode steps whose tokens reached the
host in the traced stretch, over (its seconds x the chip's bf16 peak), in
%.  FLOPs from shapes (bench/lib/flops.py): active experts only, causal
attention pairs, logits of the positions that are sampled."""

from bench.lib.readers import mfu_percent


def read(records):
    return mfu_percent(records)
