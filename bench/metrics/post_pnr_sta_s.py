"""Mean seconds of timing analysis inside post-PnR pipelining per design
whose ``post_pnr`` pass lies in the traced stretch: its ``cascade.sta``
spans (the rest of the pass is register insertion and matching)."""

from bench.lib.program_spans import per_pass, run_trace, seconds


def read(records):
    return per_pass(run_trace(records), "post_pnr", "cascade.sta", seconds)
