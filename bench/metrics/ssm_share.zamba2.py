"""Device time of the leaf ops under the ``mamba`` scope (the whole Mamba
layer: norm, projections, conv, the SSD scan, the gated norm), over
device busy time in the traced stretch, in %: the union of their
intervals per chip, averaged over the chips."""

from bench.lib.device_scopes import scope_share, trace_of


def read(records):
    return scope_share(trace_of(records), "mamba")
