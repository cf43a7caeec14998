"""Mean negotiation iterations of the router per design whose ``route``
pass lies in the traced stretch: its ``cascade.route.iter`` spans."""

from bench.lib.program_spans import per_pass, run_trace


def read(records):
    return per_pass(run_trace(records), "route", "cascade.route.iter", len)
