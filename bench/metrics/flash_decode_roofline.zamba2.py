"""Share of its roofline that the Pallas ``flash_decode`` kernel reaches on
one chip, in %: the least time its calls in the traced stretch need for
one chip's share of the KV heads (the step's cost from shapes,
bench/lib/flops.py, over the configuration's ``tensor_parallel``) over
their device time in the trace.  The trace's reduction gives each op's
seconds and calls averaged over the chips, so both sides are per chip.
Memory sets the bound.  Calls are matched by the kernel's name."""

from bench.lib.flops import roofline_seconds
from bench.lib.peaks import peaks_for
from bench.lib.readers import traced_work
from bench.lib.trace import op_seconds


def is_kernel(name: str) -> bool:
    """The kernel's custom call, ``%flash_decode.<n>`` in a TPU trace."""
    return name.startswith("%flash_decode")


def read(records):
    red = records.get("trace")
    work, _ = traced_work(records)
    steps = [w for w in work if w.get("kernel")]
    if not red or not steps:
        return None
    hit = op_seconds(red, is_kernel)
    if hit is None:
        return None
    seconds, calls = hit
    tp = records["dims"]["tensor_parallel"]
    peaks = peaks_for(records["device_kind"])
    # every call in a step has that step's shapes, a chip 1/tp of its heads
    least = sum(roofline_seconds(f / tp, b / tp, peaks.flops, peaks.hbm_bw)[0]
                for f, b in (w["kernel"] for w in steps)) / len(steps)
    return 100.0 * least * calls / seconds
