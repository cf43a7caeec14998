"""What every cell shares: the cell's files found by name, the window's
clock and trace, and the result line.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``).  The mix's ``loop`` names
the general driver that reads it (``bench/lib/<loop>.py``, a ``run(cell)``
function).  Per-layer metrics are readers ``bench/metrics/<name>.py``
with a ``read(records)`` function that returns a number, or ``None``
where the run holds nothing to read.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import os
import sys
import time
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find(items: List[dict], name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r}")


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    t_process: float                      # perf_counter at process start
    t_ready: Optional[float] = None       # ... once jax holds the chips
    meter: object = None
    out_dir: str = os.path.join(ROOT, "bench_out")
    #: a fault planted under the timed path (tests only; see bench/tests)
    fault: Optional[str] = None


def cell_from_benchmark(bench: dict, name: str, **kw) -> Cell:
    w = find(bench["workloads"], name, "workload")
    c = find(bench["configs"], w["config"], "config")
    config = load_json(os.path.join(ROOT, c["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    return Cell(name=name, config=config, traffic=traffic, chips=w["chips"],
                **kw)


def loop_module(cell: Cell):
    return importlib.import_module(f"bench.lib.{cell.traffic['loop']}")


def now() -> float:
    return time.perf_counter()


def setup_note(cell: Cell, setup_s: float, t_build: float, what: str) -> None:
    """Where set-up went: imports and reaching the chips, building, and
    ``what`` (from ``t_build`` on), on standard error."""
    ready = (cell.t_ready or cell.t_process) - cell.t_process
    print(f"set-up {setup_s:.3f} s: imports and chips {ready:.3f} s, "
          f"build {t_build - cell.t_process - ready:.3f} s, {what} "
          f"{now() - t_build:.3f} s", file=sys.stderr)


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's trace (``bench.<name>``)."""
    import jax
    with jax.profiler.TraceAnnotation("bench." + name):
        yield


class Tracer:
    """Profiler trace of part of the window, only in ``--trace 1`` runs.
    ``start()``/``stop()`` bracket the traced part with the window span;
    ``reduce()`` reads the trace back after the window."""

    def __init__(self, cell: Cell):
        self.on = cell.trace
        self.dir = os.path.join(cell.out_dir, "trace", cell.name)
        self.t0 = self.t1 = None
        self._ann = None

    def start(self):
        if not self.on or self.t0 is not None:
            return
        import shutil
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()
        self.t0 = now()

    def stop(self):
        if self.t0 is None or self.t1 is not None:
            return
        import jax
        self.t1 = now()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    @property
    def active(self) -> bool:
        return self.t0 is not None and self.t1 is None

    def reduce(self) -> Optional[dict]:
        if self.t1 is None:
            return None
        from bench.lib import trace
        red = trace.reduce_trace(trace.newest_xplane(self.dir))
        with open(os.path.join(self.dir, "reduced.json"), "w") as f:
            json.dump(red, f, indent=1)
        return red


def read_per_layer(name: str, records: dict):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(records)


def reports(metric: dict, cell: str, e2e_of_cell: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in e2e_of_cell if "moves" in metric else True


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        try:
            peak = max(peak, int(d.memory_stats()["peak_bytes_in_use"]))
        except Exception:                 # backends without memory stats
            pass
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def result_line(bench: dict, cell: Cell, out: dict) -> dict:
    """Assemble the contract's JSON object from a loop's outcome.

    ``out`` holds ``setup_s``, ``e2e`` (end-to-end values by name),
    ``records`` (what per-layer readers read), ``checks`` (name ->
    (value, limit)), ``attempted``, ``failed``, ``device`` and, in a
    traced run, ``trace`` (the reduction)."""
    e2e = {"setup_s": out["setup_s"], **out["e2e"]}
    e2e_of_cell = {m["name"] for m in bench["end_to_end"]
                   if reports(m, cell.name, set())}
    metrics: Dict[str, dict] = {}
    if not cell.trace:
        for m in bench["end_to_end"]:
            if m["name"] in e2e_of_cell and m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in bench["per_layer"]:
            if reports(m, cell.name, e2e_of_cell):
                v = read_per_layer(m["name"], out["records"])
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(out["device"])
    line = {"correct": all(v <= lim for v, lim in out["checks"].values()),
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": device}
    if cell.trace and out.get("trace"):
        from bench.lib.trace import breakdown
        red = out["trace"]
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        line["breakdown"] = breakdown(red)
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out["checks"].items()}
    return line


def print_result(line: dict) -> None:
    """The numbers compared, each beside its limit, as the last lines of
    standard error; the result as the last line of standard output."""
    print(f"correct: {line['correct']}", file=sys.stderr)
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
