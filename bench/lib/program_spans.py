"""Read what the program itself writes into this run's profiler trace: its
host spans (``cascade.*``, with their attributes) and the model scopes on
the device's ops.

The compiler wraps each pass, the placer's set-up and anneal, each router
iteration and kernel call, each pipelining round and each timing run in a
``cascade.`` span (``repro.runtime.spans``); the LM step names its layer
loop, attention, MoE and head with ``jax.named_scope``, which reaches the
TPU trace as each op's name stack (the ``tf_op`` stat).  The readers here
find the run's own trace, and return ``None`` where it holds nothing to
read: no trace, another run's trace, or a program without the spans.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Tuple

from bench.lib import trace as T
from bench.lib.harness import ROOT

TRACE_ROOT = os.path.join(ROOT, "bench_out", "trace")
PREFIX = "cascade."
NO_SPAN = "no cascade span"
#: the stat that carries an op's name stack in a TPU trace
SCOPE_STAT = "tf_op"
#: ops whose time already holds their bodies' ops
CONTAINER = re.compile(r"%?(while|conditional|call)(\.\d+)?$")
#: spans in which the host waits on the chip
DEVICE_WAIT = ("cascade.place.anneal", "cascade.route.kernel")


@dataclasses.dataclass
class Trace:
    """One trace: the window's bounds (ns), the ``cascade.`` spans as
    ``(start, end, name, attrs)``, and per device the ops as ``(start,
    end, name, name stack or None)``."""
    bounds: Optional[Tuple[float, float]]
    spans: List[Tuple[float, float, str, dict]]
    devices: Dict[str, List[Tuple[float, float, str, Optional[str]]]]


@functools.lru_cache(maxsize=1)
def _xspace_class():
    """A message class for the parts of the profiler's ``XSpace`` protobuf
    (``tsl/profiler/protobuf/xplane.proto``, the same field numbers) that
    hold the op events' metadata stats; parsing skips every other field.
    ``jax.profiler.ProfileData`` shows an event's own stats, not those of
    its metadata, where a TPU trace keeps the name stack."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def message(name, fields, into=fd.message_type):
        m = into.add(name=name)
        for fname, number, kind, label, type_name in fields:
            f = m.field.add(name=fname, number=number, type=kind, label=label)
            if type_name:
                f.type_name = ".bench_xplane." + type_name
        return m

    def map_entry(into, name, value):
        m = message(name, [("key", 1, F.TYPE_INT64, one, None),
                           ("value", 2, F.TYPE_MESSAGE, one, value)], into)
        m.options.map_entry = True

    message("XStat", [("metadata_id", 1, F.TYPE_INT64, one, None),
                      ("str_value", 5, F.TYPE_STRING, one, None),
                      ("ref_value", 7, F.TYPE_UINT64, one, None)])
    message("XEvent", [("metadata_id", 1, F.TYPE_INT64, one, None)])
    message("XLine", [("name", 2, F.TYPE_STRING, one, None),
                      ("events", 4, F.TYPE_MESSAGE, many, "XEvent")])
    message("XEventMetadata", [("name", 2, F.TYPE_STRING, one, None),
                               ("stats", 5, F.TYPE_MESSAGE, many, "XStat")])
    message("XStatMetadata", [("name", 2, F.TYPE_STRING, one, None)])
    plane = message("XPlane", [
        ("name", 2, F.TYPE_STRING, one, None),
        ("lines", 3, F.TYPE_MESSAGE, many, "XLine"),
        ("event_metadata", 4, F.TYPE_MESSAGE, many,
         "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, F.TYPE_MESSAGE, many,
         "XPlane.StatMetadataEntry")])
    map_entry(plane.nested_type, "EventMetadataEntry", "XEventMetadata")
    map_entry(plane.nested_type, "StatMetadataEntry", "XStatMetadata")
    message("XSpace", [("planes", 1, F.TYPE_MESSAGE, many, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def name_stacks(raw: bytes, device_plane: Callable[[str], bool],
                ops_line: Callable[[str, str], bool]
                ) -> Dict[str, List[Tuple[str, Optional[str]]]]:
    """Per device plane, each op event of its ops line in order, as
    ``(name, name stack or None)``: the ``tf_op`` stat of the event's
    metadata (``jit(f)/layers/while/body/attention/dot_general:`` -> the
    part before the colon)."""
    space = _xspace_class()()
    space.ParseFromString(raw)
    out = {}
    for plane in space.planes:
        if not device_plane(plane.name):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        stacks: Dict[int, Tuple[str, Optional[str]]] = {}

        def of(mid):
            if mid not in stacks:
                md = plane.event_metadata[mid]
                stack = next(((st.str_value or stat_names.get(st.ref_value,
                                                               ""))
                              for st in md.stats
                              if stat_names.get(st.metadata_id)
                              == SCOPE_STAT), None)
                stacks[mid] = (md.name,
                               stack.split(":", 1)[0] if stack else None)
            return stacks[mid]
        for line in plane.lines:
            if ops_line(plane.name, line.name):
                out[plane.name] = [of(ev.metadata_id) for ev in line.events]
    return out


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime_ns: int,
          device_plane: Callable[[str], bool],
          ops_line: Callable[[str, str], bool]) -> Trace:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    data = ProfileData.from_serialized_xspace(raw)
    windows, spans, devices = [], [], {}
    for plane in data.planes:
        for line in plane.lines:
            if device_plane(plane.name) and ops_line(plane.name, line.name):
                devices[plane.name] = [
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in line.events]
            elif T.is_host_line(plane.name, line.name):
                for ev in line.events:
                    end = ev.start_ns + ev.duration_ns
                    if ev.name == T.WINDOW_SPAN:
                        windows.append((ev.start_ns, end))
                    elif ev.name.startswith(PREFIX):
                        spans.append((ev.start_ns, end, ev.name,
                                      dict(ev.stats)))
    stacks = name_stacks(raw, device_plane, ops_line) if devices else {}
    for name, evs in devices.items():
        st = stacks.get(name, [])
        # the two readers walk the same events in the same order; where
        # they do not agree, the plane's ops carry no name stack
        if len(st) != len(evs) or any(a[2] != b[0] for a, b in zip(evs, st)):
            st = [(None, None)] * len(evs)
        devices[name] = [(s, e, T.op_name(n), stack)
                         for (s, e, n), (_, stack) in zip(evs, st)]
    bounds = ((min(s for s, _ in windows), max(e for _, e in windows))
              if windows else None)
    spans.sort()
    return Trace(bounds, spans, devices)


def load(path: str, device_plane: Callable[[str], bool] = T.is_tpu_plane,
         ops_line: Callable[[str, str], bool] = T.is_ops_line) -> Trace:
    return _load(path, os.stat(path).st_mtime_ns, device_plane, ops_line)


def find_trace(records: dict, root: Optional[str] = None) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``root/*/`` (``TRACE_ROOT``, where
    the harness's tracer writes) whose window has the bounds of this run's
    reduced trace, or ``None``."""
    red = records.get("trace")
    if not red:
        return None
    want = red["bounds_ns"]
    paths = glob.glob(os.path.join(root or TRACE_ROOT, "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        got = load(path).bounds
        if got is not None and all(abs(a - b) < 1
                                   for a, b in zip(got, want)):
            return path
    return None


def run_trace(records: dict) -> Optional[Trace]:
    path = find_trace(records)
    return None if path is None else load(path)


# ---------------------------------------------------------------------------
# compiler spans


def inside(tr: Trace, outer: Tuple[float, float, str, dict],
           name: str) -> List[Tuple[float, float, str, dict]]:
    return [sp for sp in tr.spans
            if sp[2] == name and outer[0] <= sp[0] and sp[1] <= outer[1]]


def per_pass(tr: Optional[Trace], pass_name: str, child: str,
             value: Callable[[list], float]) -> Optional[float]:
    """Mean over the ``cascade.pass.<pass_name>`` spans that lie in the
    window of ``value(child spans inside it)``; ``None`` where the window
    holds no such pass or no such child at all."""
    if tr is None or tr.bounds is None:
        return None
    lo, hi = tr.bounds
    passes = [sp for sp in tr.spans if sp[2] == PREFIX + "pass." + pass_name
              and lo <= sp[0] and sp[1] <= hi]
    kids = [inside(tr, sp, child) for sp in passes]
    if not any(kids):
        return None
    return sum(value(k) for k in kids) / len(kids)


def seconds(spans: list) -> float:
    return sum(e - s for s, e, _, _ in spans) * 1e-9


def idle_by_span(tr: Trace) -> Dict[str, float]:
    """Device idle seconds in the window (averaged over the devices) by
    the innermost ``cascade.`` span that covers each gap's midpoint.  A gap
    is first cut where a span begins or ends inside it: one gap often
    outlasts several passes, and each piece goes to its own span."""
    lo, hi = tr.bounds
    spans = [(s, e, name) for s, e, name, _ in tr.spans]
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    idle: Dict[str, float] = collections.Counter()
    n = max(1, len(tr.devices))
    for evs in tr.devices.values():
        busy = T.union([(max(s, lo), min(e, hi)) for s, e, _, _ in evs
                        if e > lo and s < hi])
        for a, b in T.gaps(busy, lo, hi):
            inner = cuts[bisect.bisect_right(cuts, a):
                         bisect.bisect_left(cuts, b)]
            edges = [a, *inner, b]
            for piece in zip(edges, edges[1:]):
                where = T.label(piece, spans)
                where = NO_SPAN if where == T.NO_SPAN else where
                idle[where] += (piece[1] - piece[0]) / n * 1e-9
    return dict(idle)


def host_idle_percent(tr: Optional[Trace]) -> Optional[float]:
    """Share of the window, in %, in which the device idles while the host
    is inside a ``cascade.`` span other than those in which it waits on
    the device (the anneal, the router's kernel calls)."""
    if tr is None or tr.bounds is None or not tr.devices or not tr.spans:
        return None
    lo, hi = tr.bounds
    idle = idle_by_span(tr)
    host = sum(v for k, v in idle.items()
               if k != NO_SPAN and k not in DEVICE_WAIT)
    return 100.0 * host / ((hi - lo) * 1e-9)


# ---------------------------------------------------------------------------
# model scopes


def scope_of(stack: Optional[str]) -> set:
    """The scope names on an op's name stack (``jit(f)/layers/while/body/
    attention/dot_general`` -> its components)."""
    return set(stack.split("/")) if stack else set()


def scope_seconds(tr: Optional[Trace]) -> Optional[Dict[str, float]]:
    """Device seconds in the window of the leaf ops under ``attention``,
    under ``moe``, and under ``layers`` but neither (``layer_carry``), each
    the union of its ops' intervals, averaged over the devices; with
    ``busy``, the union of all ops.  ``None`` where no op in the window
    carries the ``layers`` scope (no name stacks, or no scopes), and where
    an op's stack holds a loop outside ``layers``: the layer loop is the
    only loop outside the kernels, so that op's program was compiled
    without the scopes.  (JAX's persistent compile cache keys a program
    without its debug information, so an executable compiled before the
    scopes existed keeps serving the same program, with its old name
    stacks.)"""
    if tr is None or tr.bounds is None or not tr.devices:
        return None
    lo, hi = tr.bounds
    out: Dict[str, float] = collections.Counter()
    n = len(tr.devices)
    named = unscoped = False
    for evs in tr.devices.values():
        parts: Dict[str, list] = collections.defaultdict(list)
        for s, e, name, stack in evs:
            if e <= lo or s >= hi:
                continue
            iv = (max(s, lo), min(e, hi))
            parts["busy"].append(iv)
            if CONTAINER.match(name):
                continue
            sc = scope_of(stack)
            named = named or "layers" in sc
            unscoped = unscoped or ("while" in sc and "layers" not in sc)
            if "attention" in sc:
                parts["attention"].append(iv)
            elif "moe" in sc:
                parts["moe"].append(iv)
            elif "layers" in sc:
                parts["layer_carry"].append(iv)
        for k, ivs in parts.items():
            out[k] += sum(e - s for s, e in T.union(ivs)) / n * 1e-9
    return dict(out) if named and not unscoped else None


def scope_share(tr: Optional[Trace], scope: str) -> Optional[float]:
    """``scope``'s device seconds over device busy seconds, in %."""
    secs = scope_seconds(tr)
    if not secs or secs.get("busy", 0) <= 0:
        return None
    return 100.0 * secs.get(scope, 0.0) / secs["busy"]
