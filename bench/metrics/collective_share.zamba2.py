"""Device time of the collective ops (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all, and their -start/-done
halves), over device busy time in the traced stretch, in %: the union of
their intervals per chip, averaged over the chips."""

from bench.lib.device_scopes import collective_share, trace_of


def read(records):
    return collective_share(trace_of(records))
