"""Device time of the leaf ops under the ``shared_block`` scope (its norms,
attention with its cache writes and flash_decode, MLP, adapter and
linear), over device busy time in the traced stretch, in %."""

from bench.lib.device_scopes import scope_share, trace_of


def read(records):
    return scope_share(trace_of(records), "shared_block")
