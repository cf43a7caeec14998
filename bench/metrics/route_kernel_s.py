"""Mean seconds of the router's kernel calls per design whose ``route``
pass lies in the traced stretch: the ``cascade.route.kernel`` spans, each
from the call through the read-back of its results (the rest of the pass
is the host's rip-up, re-pricing and tree building)."""

from bench.lib.program_spans import per_pass, run_trace, seconds


def read(records):
    return per_pass(run_trace(records), "route", "cascade.route.kernel",
                    seconds)
