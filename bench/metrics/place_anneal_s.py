"""Mean seconds of the placer's anneal per design whose ``place`` pass
lies in the traced stretch: the ``cascade.place.anneal`` span, from the
jitted anneal's call through the read-back of its results (the device
loop, its dispatch and its read-back; the placer's host set-up is
``cascade.place.setup``)."""

from bench.lib.program_spans import per_pass, run_trace, seconds


def read(records):
    return per_pass(run_trace(records), "place", "cascade.place.anneal",
                    seconds)
