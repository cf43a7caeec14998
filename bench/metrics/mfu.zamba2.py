"""Zamba2's model FLOPs of the prefill and decode steps whose tokens
reached the host in the traced stretch (bench/lib/zamba2_flops.py: each
step's batch and length recovered from its flash_decode cost), over
(its seconds x one chip's bf16 peak x the configuration's
``tensor_parallel`` chips), in %."""

from bench.lib.peaks import peaks_for
from bench.lib.zamba2_flops import step_flops


def read(records):
    t0, t1 = records.get("tracer", (None, None))
    if t0 is None or t1 is None or t1 <= t0:
        return None
    dims, work = records["dims"], records.get("work", [])
    # a prefill whose first decode step is not in the run tells nothing
    flops = [f for f in (step_flops(dims, work, i)
                         for i, w in enumerate(work) if t0 <= w["t"] <= t1)
             if f is not None]
    if not flops:
        return None
    chips = dims["tensor_parallel"]
    peak = peaks_for(records["device_kind"]).flops
    return 100.0 * sum(flops) / ((t1 - t0) * peak * chips)
