"""Share of the ``flash_decode`` grid's cache blocks that the kernel
copies over the traced steps, in %: per step, the sum over its rows of
cdiv(frontier, bk), over B x kv_steps, with (bk, kv_steps) from the
program's own ``decode_plan`` at one chip's KV heads (the configuration's
``tensor_parallel`` splits them), the head dim and the cache's length.

A step's batch and frontier come from its kernel cost
(``bench/lib/zamba2_flops.batch_and_length``: every row of a step has one
frontier); the cache's length is the prompt plus generated tokens of the
serve shape, among the traffic of this configuration's cells, whose batch
is the step's and whose decode steps reach that frontier.  ``None`` where
the program has no ``decode_plan`` (it then reads the whole cache) or no
step tells its shape."""

import os

from bench.lib.harness import BENCH, benchmark, load_json
from bench.lib.readers import traced_work
from bench.lib.zamba2_flops import batch_and_length


def _cache_lengths(config_name: str, batch: int, frontier: int) -> set:
    """Lengths of the caches a step of ``batch`` rows at ``frontier`` may
    read in this configuration's serve cells."""
    bench = benchmark()
    out = set()
    for w in bench["workloads"]:
        if w["config"] != config_name:
            continue
        tr = load_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        for b, p, g in tr.get("shapes", ()):
            if b == batch and p < frontier <= p + g:
                out.add(p + g)
    return out


def read(records):
    try:
        from repro.kernels.flash_decode.flash_decode import decode_plan
    except ImportError:
        return None
    import jax.numpy as jnp
    dims = records["dims"]
    kv_all = dims["num_key_value_heads"]
    kv = kv_all // dims.get("tensor_parallel", 1)
    g = dims["num_attention_heads"] // kv_all
    itemsize = jnp.dtype(dims["dtype"]).itemsize
    work, _ = traced_work(records)
    read_blocks = grid_blocks = 0
    for w in work:
        if not w.get("kernel"):
            continue
        b, frontier = batch_and_length(dims, w["kernel"])
        lengths = _cache_lengths(dims["name"], b, frontier)
        if len(lengths) != 1:
            continue
        bk, kv_steps = decode_plan(kv, g, dims["head_dim"], lengths.pop(),
                                   itemsize)
        read_blocks += b * -(-frontier // bk)
        grid_blocks += b * kv_steps
    if not grid_blocks:
        return None
    return 100.0 * read_blocks / grid_blocks
