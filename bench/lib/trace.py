"""Reduce one JAX profiler trace to device busy time, device time per op
and idle gaps labelled with the host span they fell in.

The harness wraps the traced part of its window in a host span
``bench.window`` and each of its own calls into the program in a span
``bench.<what>`` (``jax.profiler.TraceAnnotation``), so the trace carries
both clocks.  Busy time is the union of the intervals in which an op ran
on a device; idle is the rest of the window.  Each idle gap is labelled
with the innermost ``bench.`` span that covers its midpoint, or
``no bench span`` where none does.
"""

from __future__ import annotations

import collections
import glob
import os
from typing import Callable, Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
NO_SPAN = "no bench span"

Interval = Tuple[float, float]


def newest_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def is_tpu_plane(plane: str) -> bool:
    return plane.startswith("/device:TPU:")


def is_ops_line(plane: str, line: str) -> bool:
    return line == "XLA Ops"


def is_host_line(plane: str, line: str) -> bool:
    """Any host thread: the ``bench.`` prefix picks the spans out."""
    return plane.startswith("/host:")


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: Interval, spans: List[Tuple[float, float, str]]) -> str:
    """Innermost span (shortest) covering the gap's midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    best: Optional[Tuple[float, str]] = None
    for s, e, name in spans:
        if s <= mid <= e and name != WINDOW_SPAN:
            if best is None or e - s < best[0]:
                best = (e - s, name)
    return best[1] if best else NO_SPAN


def op_name(event_name: str) -> str:
    """The op's name without the HLO text the TPU trace appends to it
    (``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``)."""
    return event_name.split(" = ", 1)[0]


def read_events(path: str,
                device_plane: Callable[[str], bool] = is_tpu_plane,
                ops_line: Callable[[str, str], bool] = is_ops_line,
                host_line: Callable[[str, str], bool] = is_host_line):
    """(device op events per device plane, bench host spans), each event
    ``(start_ns, end_ns, name)``."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    devices: Dict[str, List[Tuple[float, float, str]]] = {}
    spans: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        for line in plane.lines:
            if device_plane(plane.name) and ops_line(plane.name, line.name):
                evs = devices.setdefault(plane.name, [])
                for ev in line.events:
                    evs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                op_name(ev.name)))
            elif host_line(plane.name, line.name):
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    return devices, spans


def reduce_events(devices: Dict[str, List[Tuple[float, float, str]]],
                  spans: List[Tuple[float, float, str]]) -> dict:
    """Busy and window seconds averaged over the devices, device seconds
    and calls per op name (summed over devices, divided by their number),
    idle seconds per host span label, and the window's bounds in ns."""
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        evs = [ev for d in devices.values() for ev in d]
        if not evs:
            raise ValueError("trace has neither a window span nor device ops")
        lo, hi = min(ev[0] for ev in evs), max(ev[1] for ev in evs)
    n = max(1, len(devices))
    busy_ns = 0.0
    ops: Dict[str, float] = collections.Counter()
    calls: Dict[str, float] = collections.Counter()
    idle: Dict[str, float] = collections.Counter()
    longest: List[Tuple[float, str]] = []
    for evs in devices.values():
        inside = [(max(s, lo), min(e, hi), name) for s, e, name in evs
                  if e > lo and s < hi]
        for s, e, name in inside:
            ops[name] += (e - s) / n
            calls[name] += 1 / n
        busy = union([(s, e) for s, e, _ in inside])
        busy_ns += sum(e - s for s, e in busy)
        for g in gaps(busy, lo, hi):
            where = label(g, spans)
            idle[where] += (g[1] - g[0]) / n
            longest.append((g[1] - g[0], where))
    longest.sort(reverse=True)
    return {
        "devices": len(devices),
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns / n * 1e-9,
        "op_s": {k: v * 1e-9 for k, v in ops.items()},
        "op_calls": dict(calls),
        "idle_s_by_span": {k: v * 1e-9 for k, v in idle.items()},
        "longest_gaps": [(w, d * 1e-9) for d, w in longest[:10]],
        "bounds_ns": (lo, hi),
    }


def reduce_trace(path: str, **kw) -> dict:
    return reduce_events(*read_events(path, **kw))


def breakdown(red: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device ops that took most time,
    and the idle seconds by what the host was doing."""
    ops = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(red["idle_s_by_span"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}


def op_seconds(red: dict, match: Callable[[str], bool]):
    """(device seconds, calls) of the ops whose name ``match`` accepts, or
    ``None`` where the trace holds none."""
    hits = [k for k in red["op_s"] if match(k)]
    if not hits:
        return None
    return (sum(red["op_s"][k] for k in hits),
            sum(red["op_calls"][k] for k in hits))
