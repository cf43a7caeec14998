"""Arithmetic shared by the per-layer readers in ``bench/metrics/``."""

from __future__ import annotations

from typing import Optional

import numpy as np


def mean_of(records: dict, field) -> Optional[float]:
    """Mean over the window's designs of ``field(design record)``."""
    vals = [field(d) for d in records.get("designs", ())]
    return float(np.mean(vals)) if vals else None


def idle_percent(records: dict) -> Optional[float]:
    red = records.get("trace")
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def traced_work(records: dict):
    """The steps whose tokens reached the host inside the traced stretch,
    and its length in seconds (host clock)."""
    t0, t1 = records.get("tracer", (None, None))
    if t0 is None or t1 is None:
        return [], 0.0
    return [w for w in records.get("work", ()) if t0 <= w["t"] <= t1], t1 - t0


def mfu_percent(records: dict) -> Optional[float]:
    """Model FLOPs of the traced steps over (traced seconds x peak)."""
    from bench.lib.peaks import peaks_for
    work, secs = traced_work(records)
    if not work or secs <= 0:
        return None
    peak = peaks_for(records["device_kind"]).flops
    return 100.0 * sum(w["flops"] for w in work) / (secs * peak)
