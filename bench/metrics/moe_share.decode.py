"""Device time of the leaf ops under the ``moe`` scope (routing, dispatch
and combine gathers, the expert matmuls), over device busy time in the
traced stretch, in %: the union of their intervals, from each op's name
stack in the trace."""

from bench.lib.program_spans import run_trace, scope_share


def read(records):
    return scope_share(run_trace(records), "moe")
