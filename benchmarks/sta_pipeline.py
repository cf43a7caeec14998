"""Vectorized STA / incremental re-timing benchmark: scalar vs numpy.

Times the post-PnR register-insertion loop (paper Section V-D) — the
inner loop of every power-cap and Pareto-frontier sweep — under each
``sta_backend``, on the routed benchmark designs.  The contract is
*asserted*, not just printed:

* the numpy engine's one-shot STA report is bit-identical to the scalar
  oracle (critical path ns, reconstruction, arrival maps, segments);
* the pipelining loop is byte-identical across the two engines (same histories,
  stop reasons, register placements);
* the numpy incremental engine reaches >= 5x warm speedup over the
  scalar loop on the headline app (harris x4).

Timing protocol: the routed design and the lowering are built *outside*
the timer (the lowering is structure-only, so one serves every run); a
throwaway warm run per backend absorbs one-time costs; the reported
number is the best of three timed runs of
the full loop on a fresh deepcopy.

The end-to-end section sweeps a small Pareto grid through
``explore_frontier`` with scalar vs numpy engines — every frontier
point shares one lowering — and asserts identical frontiers.

A capture-hoist note for the archaeology: profiling this loop showed the
old per-round ``DesignCheckpoint.capture`` (a full reg-state snapshot,
O(total hops)) dominating round overhead; rounds now record a positional
``_RoundDelta`` (branch counts + the sites actually added) and only the
power-cap hook still captures full checkpoints, at its accept points.

    PYTHONPATH=src python -m benchmarks.sta_pipeline [--fast]
        [--bench-out BENCH_sta.json]

``benchmarks.run`` drives this as the ``sta`` section and folds the rows
into its trajectory record; CI uploads ``BENCH_sta.json`` from the
perf-smoke lane.
"""

from __future__ import annotations

import argparse
import copy
import time
from typing import Dict, List, Optional, Tuple

from benchmarks._util import append_bench_record, print_csv

#: (app, unroll) pairs, smallest to largest; harris x4 is the headline
#: (the ISSUE's >= 5x pipelining-loop criterion is checked against it).
BENCH_APPS = (("gaussian", 1), ("camera", 2), ("harris", 1),
              ("mttkrp", 2), ("harris", 4))
FAST_APPS = (("gaussian", 1), ("harris", 4))
HEADLINE = "harrisx4"
SPEEDUP_BAR = 5.0
REPEATS = 3


def _routed(compiler, app: str, mult: int):
    from repro.core import ALL_APPS, PassConfig

    art = compiler.compile_to_stage(ALL_APPS[app], PassConfig(),
                                    stage="routed", unroll=mult)
    return art.state["design"], art.state["place_timing"]


def _assert_reports_identical(name: str, ref, got) -> None:
    ok = (got.critical_path_ns == ref.critical_path_ns
          and got.max_freq_mhz == ref.max_freq_mhz
          and got.n_segments == ref.n_segments
          and got.critical_path == ref.critical_path
          and got.arrival_out == ref.arrival_out)
    assert ok, f"{name}: vectorized STA diverged from the scalar oracle"


def _loop_state(design, res) -> Tuple:
    return (tuple(res.history), res.stop_reason, res.registers_added,
            tuple(sorted((k, tuple(sorted(rb.reg_hops)))
                         for k, rb in design.routes.items())),
            tuple(b.n_regs for b in design.netlist.branches))


def _time_loop(design, tm, backend: str, lowering=None) -> Tuple[float, Tuple]:
    """Best-of-N wall time for one full pipelining loop; deepcopy and
    lowering stay outside the timer."""
    from repro.core import post_pnr_pipeline

    best, state = float("inf"), None
    for _ in range(1 + REPEATS):          # first run is the warmup
        d = copy.deepcopy(design)
        t0 = time.perf_counter()
        res = post_pnr_pipeline(d, tm, sta_backend=backend,
                                lowering=lowering)
        dt = time.perf_counter() - t0
        if state is None:                 # warmup: keep the state, not time
            state = _loop_state(d, res)
            continue
        assert _loop_state(d, res) == state, \
            f"{backend}: loop not deterministic across runs"
        best = min(best, dt)
    return best, state


def bench_pipelining(fast: bool = False) -> List[Dict]:
    from repro.core import (CascadeCompiler, CompileCache, analyze,
                            lower_design)

    compiler = CascadeCompiler(cache=CompileCache())

    rows: List[Dict] = []
    for app, mult in (FAST_APPS if fast else BENCH_APPS):
        name = f"{app}x{mult}"
        design, tm = _routed(compiler, app, mult)
        # one-shot bit-identity gate
        _assert_reports_identical(name, analyze(design, tm),
                                  analyze(design, tm, backend="numpy"))
        lowering = lower_design(design, tm)
        t_scalar, s_scalar = _time_loop(design, tm, "scalar")
        t_vec, s_vec = _time_loop(design, tm, "numpy", lowering=lowering)
        assert s_vec == s_scalar, \
            f"{name}: numpy loop diverged from the scalar loop"
        rows.append({"app": name,
                     "routes": len(design.routes),
                     "rounds": len(s_scalar[0]),
                     "scalar_s": round(t_scalar, 4),
                     "numpy_s": round(t_vec, 4),
                     "numpy_speedup": round(t_scalar / t_vec, 2)})
    print_csv(rows, "post-PnR pipelining loop, scalar vs vectorized STA "
                    "(wall seconds, best of %d)" % REPEATS)
    return rows


def bench_explore(fast: bool = False) -> Dict:
    """End-to-end: a Pareto sweep with every frontier point re-timed by
    the shared-lowering numpy engine vs the scalar oracle."""
    from repro.core import (ALL_APPS, CascadeCompiler, CompileCache,
                            ExploreSpec, explore_frontier)

    app, mult = ("harris", 1) if fast else ("harris", 4)
    compiler = CascadeCompiler(cache=CompileCache())
    design, tm = _routed(compiler, app, mult)
    iters = ALL_APPS[app].iterations_for(mult)
    spec = ExploreSpec(register_budgets=(2, 6, None))

    def run(backend: str) -> Tuple[float, Tuple]:
        d = copy.deepcopy(design)
        t0 = time.perf_counter()
        fr = explore_frontier(d, tm, compiler.energy, iters, spec,
                              sta_backend=backend)
        dt = time.perf_counter() - t0
        pts = tuple(tuple(sorted(p.scaled().items()))
                    for p in fr.all_points())
        return dt, (pts, _loop_state(d, fr.selected.result.post_pnr))

    t_scalar, f_scalar = run("scalar")
    run("numpy")                          # warmup (lowering + caches)
    t_numpy, f_numpy = run("numpy")
    assert f_numpy == f_scalar, "explore frontier diverged across engines"
    out = {"app": f"{app}x{mult}", "points": len(spec.points()),
           "scalar_s": round(t_scalar, 3), "numpy_s": round(t_numpy, 3),
           "speedup": round(t_scalar / t_numpy, 2)}
    print(f"[sta_pipeline] explore_frontier {out['app']} "
          f"({out['points']} points): scalar {out['scalar_s']}s, "
          f"numpy {out['numpy_s']}s ({out['speedup']}x)")
    return out


def run_all(fast: bool = False) -> Dict:
    rows = bench_pipelining(fast=fast)
    headline = next((r for r in rows if r["app"] == HEADLINE), rows[-1])
    speedup = headline.get("numpy_speedup", 0.0)
    print(f"[sta_pipeline] {headline['app']}: pipelining loop "
          f"{speedup}x warm (numpy incremental vs scalar)")
    assert speedup >= SPEEDUP_BAR, (
        f"{headline['app']}: numpy incremental loop speedup {speedup}x "
        f"below the {SPEEDUP_BAR}x bar")
    explore = bench_explore(fast=fast)
    return {"apps": rows, "headline_speedup": speedup, "explore": explore}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smallest + headline app only")
    ap.add_argument("--bench-out", default="BENCH_sta.json",
                    help="trajectory file to append the results to")
    args = ap.parse_args()
    out = run_all(fast=args.fast)
    append_bench_record(args.bench_out, {"sta_pipeline": out})


if __name__ == "__main__":
    main()
