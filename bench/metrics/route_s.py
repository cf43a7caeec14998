"""Mean seconds of the ``route`` pass per design in the window (the
compiler's own pass timer, host clock)."""

from bench.lib.readers import mean_of


def read(records):
    return mean_of(records, lambda d: d["pass_times"]["route"])
