"""Cut the small TPU trace that test_program_spans.py reads from the trace
of a traced ``granite.decode`` run on the chip:

    JAX_PLATFORMS=cpu python bench/tests/cut_tpu_trace.py <run's .xplane.pb>

Keeps the ``bench.window`` span and, of the first TPU's ``XLA Ops``, the
ops of one decode step (the second ``jit_decode`` program the trace ran);
of each kept op's metadata stats only the name stack (``tf_op``).  The cut
is written to ``bench/tests/data/tpu_decode_step.xplane.pb``.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def keep_only(line, keep) -> None:
    kept = [ev for i, ev in enumerate(line.events) if i in keep]
    del line.events[:]
    line.events.extend(kept)


def prune_metadata(plane, stat: str = None) -> None:
    """Drop the event metadata no kept event refers to; with ``stat``,
    every metadata stat but that one."""
    used = {ev.metadata_id for line in plane.lines for ev in line.events}
    for mid in [m for m in plane.event_metadata if m not in used]:
        del plane.event_metadata[mid]
    if stat is not None:
        ids = {k for k, v in plane.stat_metadata.items() if v.name == stat}
        for md in plane.event_metadata.values():
            kept = [st for st in md.stats if st.metadata_id in ids]
            del md.stats[:]
            md.stats.extend(kept)


def main(src: str) -> None:
    from jax.profiler import ProfileData
    from bench.lib import program_spans as P
    from bench.lib import trace as T
    with open(src, "rb") as f:
        raw = f.read()
    data = ProfileData.from_serialized_xspace(raw)
    tpu = next(p for p in data.planes if p.name == "/device:TPU:0")
    lines = {line.name: list(line.events) for line in tpu.lines}
    step = [ev for ev in lines["XLA Modules"]
            if ev.name.startswith("jit_decode")][1]
    lo, hi = step.start_ns, step.start_ns + step.duration_ns
    ops = {i for i, ev in enumerate(lines["XLA Ops"])
           if lo <= ev.start_ns and ev.start_ns + ev.duration_ns <= hi}

    space = P._xspace_class()()
    space.ParseFromString(raw)
    out = []
    for plane in space.planes:
        if plane.name == "/device:TPU:0":
            line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
            keep_only(line, ops)
            del plane.lines[:]
            plane.lines.add().CopyFrom(line)
            prune_metadata(plane, P.SCOPE_STAT)
        elif T.is_host_line(plane.name, ""):
            for line in plane.lines:
                keep_only(line, {
                    i for i, ev in enumerate(line.events)
                    if plane.event_metadata[ev.metadata_id].name
                    == T.WINDOW_SPAN})
            kept = [ln for ln in plane.lines if ln.events]
            del plane.lines[:]
            plane.lines.extend(kept)
            prune_metadata(plane)
        else:
            continue
        out.append(plane)
    del space.planes[:]
    space.planes.extend(out)
    with open(os.path.join(HERE, "data", "tpu_decode_step.xplane.pb"),
              "wb") as f:
        f.write(space.SerializeToString())


if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    main(sys.argv[1])
