"""Mean registers that post-PnR pipelining added per design in the
window."""

from bench.lib.readers import mean_of


def read(records):
    return mean_of(records, lambda d: d["registers_added"])
