"""Shares of a traced stretch's device busy time, from the program's own
trace (``program_spans.run_trace``): the leaf ops under any named scope of
the model step, and the collectives.  Each is the union of its ops'
intervals per device, averaged over the devices, over device busy time,
in %; ``None`` where the trace holds nothing to read."""

from __future__ import annotations

import re
from typing import Callable, Optional

from bench.lib import program_spans as PS
from bench.lib import trace as T

#: collective ops in a TPU trace, synchronous or as -start/-done pairs
COLLECTIVE = re.compile(r"%?(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?"
                        r"(\.\d+)?$")


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(name))


def _share(tr: Optional[PS.Trace],
           pick: Callable[[str, set], bool]) -> Optional[float]:
    """Union of the window's leaf ops that ``pick(name, scopes)`` accepts
    over the union of all its ops, summed over the devices, in %.  ``None``
    where no op carries the ``layers`` scope (no name stacks), and where an
    op's stack holds a loop outside ``layers``: its program was compiled
    without the scopes (as ``program_spans.scope_seconds`` reads it)."""
    if tr is None or tr.bounds is None or not tr.devices:
        return None
    lo, hi = tr.bounds
    busy = hit = 0.0
    named = unscoped = False
    for evs in tr.devices.values():
        every, mine = [], []
        for s, e, name, stack in evs:
            if e <= lo or s >= hi:
                continue
            iv = (max(s, lo), min(e, hi))
            every.append(iv)
            sc = PS.scope_of(stack)
            if PS.CONTAINER.match(name):
                continue
            named = named or "layers" in sc
            unscoped = unscoped or ("while" in sc and "layers" not in sc)
            if pick(name, sc):
                mine.append(iv)
        busy += sum(e - s for s, e in T.union(every))
        hit += sum(e - s for s, e in T.union(mine))
    if not named or unscoped or busy <= 0:
        return None
    return 100.0 * hit / busy


def scope_share(tr: Optional[PS.Trace], scope: str) -> Optional[float]:
    """Device time of the leaf ops with ``scope`` on their name stack."""
    return _share(tr, lambda name, sc: scope in sc)


def collective_share(tr: Optional[PS.Trace]) -> Optional[float]:
    """Device time of the collective ops."""
    return _share(tr, lambda name, sc: is_collective(name))


def trace_of(records: dict) -> Optional[PS.Trace]:
    return PS.run_trace(records)
