"""Seconds of XLA compilation inside the window (JAX's monitoring
events, counted by ``bench/lib/meter.py``): shapes the set-up did not
warm, which a designer's sweep pays for too."""


def read(records):
    w = records.get("in_window")
    return None if w is None else float(w["xla_compile_s"])
