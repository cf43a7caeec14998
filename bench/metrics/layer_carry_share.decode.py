"""Device time of the leaf ops under the ``layers`` scope but under
neither ``attention`` nor ``moe``, over device busy time in the traced
stretch, in %: the layer loop's own work, the slicing, stacking and
copying of the cache around the blocks, and the norms."""

from bench.lib.program_spans import run_trace, scope_share


def read(records):
    return scope_share(run_trace(records), "layer_carry")
