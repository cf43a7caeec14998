"""Share of the traced stretch, in %, in which the device idles while the
compiler works on the host: idle gaps whose innermost ``cascade.`` span
is neither the anneal nor a router kernel call (in those two the host
waits on the chip).  Gaps outside every ``cascade.`` span (the
benchmark's own work between compiles) are not counted."""

from bench.lib.program_spans import host_idle_percent, run_trace


def read(records):
    return host_idle_percent(run_trace(records))
