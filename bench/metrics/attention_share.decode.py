"""Device time of the leaf ops under the ``attention`` scope (projections,
rope, the cache update, the attention itself), over device busy time in
the traced stretch, in %: the union of their intervals, from each op's
name stack in the trace."""

from bench.lib.program_spans import run_trace, scope_share


def read(records):
    return scope_share(run_trace(records), "attention")
