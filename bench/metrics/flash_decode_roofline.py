"""Share of its roofline that the Pallas ``flash_decode`` kernel reaches,
in %: the least time its calls in the traced stretch need (the larger of
FLOPs over peak and bytes over peak bandwidth, per call, from shapes:
bench/lib/flops.py, only the cache rows below each frontier) over their
device time in the trace.  Memory sets the bound at every decode shape
served here.  Calls are matched by the kernel's name in the trace."""

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
    peaks = peaks_for(records["device_kind"])
    # every layer's call in a step has that step's shapes
    least = sum(roofline_seconds(f, b, peaks.flops, peaks.hbm_bw)[0]
                for f, b in (w["kernel"] for w in steps)) / len(steps)
    return 100.0 * least * calls / seconds
