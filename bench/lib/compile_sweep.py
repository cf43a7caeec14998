"""A designer's placement-seed sweep: compile the configuration's CGRA
application again and again, one compile after the other (one client,
closed loop), and check the designs against the application.

The traffic file gives the compiler's settings (``pnr_backend``,
``sta_backend``, ``verify``).  Compile ``i`` of the window takes the
placement seed ``hash(--seed, i)``, odd, with fresh compile caches and
``use_cache=False``: a new design every time, as a designer's sweep
brings.  Set-up compiles ``warmup_designs`` designs on even seeds, which
the window never draws, and then calls the router's jitted kernel at
every padded shape its negotiation can bring for this netlist (powers of
two up to the drivers and sinks of its first pass), so that nothing
compiles inside the window; the run still counts XLA compiles there.
"""

from __future__ import annotations

import dataclasses
import gc
import sys
from typing import Dict, List

import numpy as np

from bench.lib import cgra_ref
from bench.lib.harness import (Cell, Tracer, device_info, now,
                               setup_note, span)


def build_app(config: dict):
    """The configuration's application, as the compiler takes it."""
    app = config["app"]
    if "lower_block" in app:
        from repro.configs import get_config
        from repro.core.lmmap import lower_block
        return lower_block(get_config(config["arch"]), **app["lower_block"])
    from repro.core import ALL_APPS
    spec = ALL_APPS[app["name"]]
    if spec.unroll != app["unroll"] or tuple(spec.frame) != tuple(
            app["frame"]):
        raise ValueError(f"{app['name']}: the program's app is unroll "
                         f"{spec.unroll}, frame {spec.frame}; the "
                         f"configuration says {app}")
    return spec


def plant(fault: str, design) -> None:
    """Break a design where it is produced (tests and the control).  The
    control drops a pipelining register from a dense design and alters an
    op of a sparse one, whose streams carry no latency."""
    nl = design.netlist
    if fault == "control":
        fault = "alter_op" if nl.sparse else "drop_register"
    if fault == "drop_register":
        b = next(b for b in nl.branches if b.n_regs > b.n_regs_init)
        b.n_regs -= 1
    elif fault == "alter_op":
        for nd in nl.nodes.values():
            if nd.kind == "pe" and nd.op in ("add", "sub", "max", "mul"):
                nd.op = {"add": "sub", "sub": "add", "max": "min",
                         "mul": "add"}[nd.op]
                return
        raise ValueError("no op to alter")
    elif fault == "misplace":
        a, b = [n for n, nd in nl.nodes.items() if nd.kind == "pe"][:2]
        design.placement[a] = design.placement[b]
    elif fault == "wrong_cp":
        pass                              # applied to the reported timing
    else:
        raise ValueError(f"unknown fault {fault!r}")


class Sweep:
    def __init__(self, cell: Cell):
        from repro.core import PassConfig
        self.cell = cell
        self.app = build_app(cell.config)
        tr = cell.traffic
        self.base = PassConfig.full(pnr_backend=tr["pnr_backend"],
                                    sta_backend=tr["sta_backend"])

    def compile(self, seed: int) -> dict:
        from repro.core import CascadeCompiler, CompileCache
        cfg = dataclasses.replace(self.base, seed=seed)
        compiler = CascadeCompiler(cache=CompileCache(),
                                   stage_cache=CompileCache())
        t0 = now()
        with span("compile"):
            res = compiler.compile(self.app, cfg,
                                   verify=self.cell.traffic["verify"],
                                   use_cache=False)
        t1 = now()
        cp = res.sta.critical_path_ns
        if self.cell.fault:
            plant(self.cell.fault, res.design)
            if self.cell.fault == "wrong_cp":
                cp = cp * 0.9
        ps = res.pass_stats
        return {"seed": seed, "t0": t0, "t1": t1, "cp_ns": cp,
                "pass_times": ps["pass_times"],
                "best_cost": ps["pnr"]["place"]["best_cost"],
                "registers_added": ps["post_pnr"]["registers_added"],
                "design": res.design}


def placement_seed(seed: int, i: int, warm: bool = False) -> int:
    """``hash(seed, i)``: odd for the window's compiles, even for set-up's."""
    h = int(np.random.SeedSequence([seed, i]).generate_state(1)[0])
    return 2 * (h >> 2) + (0 if warm else 1)


def warm_router(compile_some) -> int:
    """Run ``compile_some()`` with the router's kernel factory watched,
    then call its kernel at every padded shape up to the largest batch
    (drivers ``D``, sinks ``S``) that the watched compiles routed, on the
    same tile tables: pad drivers with no sinks, which route nothing.
    Returns the number of shapes called."""
    import jax.numpy as jnp
    from repro.core import route_jax
    make = route_jax._jitted_router
    seen: Dict[int, list] = {}

    def watch(T, D, S):
        kernel = make(T, D, S)

        def call(*args):
            seen.setdefault(T, []).append((D, S, args[:3]))
            return kernel(*args)
        return call

    route_jax._jitted_router = watch
    try:
        compile_some()
    finally:
        route_jax._jitted_router = make
    pow2 = lambda k: [1 << j for j in range(k.bit_length()) if 1 << j <= k]
    n = 0
    for T, calls in seen.items():
        tables = calls[0][2]
        for D in pow2(max(c[0] for c in calls)):
            for S in pow2(max(c[1] for c in calls)):
                paths, _ = make(T, D, S)(
                    *tables, jnp.zeros(D, jnp.int32),
                    jnp.full((D, S), -1, jnp.int32))
                paths.block_until_ready()
                n += 1
    return n


def _gc_watcher(pauses: List[float]):
    """A ``gc.callbacks`` hook that appends the seconds of each full
    (generation 2) collection to ``pauses``."""
    began = [0.0]

    def watch(phase, info):
        if info["generation"] == 2:
            if phase == "start":
                began[0] = now()
            else:
                pauses.append(now() - began[0])
    return watch


def run(cell: Cell) -> dict:
    import jax
    tr = cell.traffic
    sweep = Sweep(cell)
    t_warm = now()
    shapes = warm_router(lambda: [
        sweep.compile(placement_seed(cell.seed, j, warm=True))
        for j in range(tr["warmup_designs"])])
    jax.effects_barrier()
    setup_s = now() - cell.t_process
    setup_note(cell, setup_s, t_warm,
               f"warm-up designs and {shapes} router shapes")

    tracer = Tracer(cell)
    designs: List[dict] = []
    before = cell.meter.snapshot()
    pauses: List[float] = []              # full collections in the window
    gc_watch = _gc_watcher(pauses)
    gc.callbacks.append(gc_watch)
    t_start = now()
    deadline = t_start + cell.seconds
    while now() < deadline:
        if tracer.on and tracer.t0 is None and \
                now() >= t_start + tr["trace_after_s"]:
            tracer.start()
        designs.append(sweep.compile(placement_seed(cell.seed, len(designs))))
        if tracer.active and now() >= tracer.t0 + tr["trace_seconds"]:
            tracer.stop()
    t_end = now()
    gc.callbacks.remove(gc_watch)
    tracer.stop()
    in_window = cell.meter.since(before)
    window = t_end - t_start

    fabric, tech = cell.config["fabric"], cell.config["timing_ns"]
    for d in designs:                     # the benchmark's own timing
        d["bench_cp_ns"] = cgra_ref.critical_path_ns(d["design"], fabric, tech)
    e2e = {"compile_s": window / len(designs),
           "design_cp_ns": float(np.mean([d["bench_cp_ns"] for d in designs]))}
    device = device_info(cell.chips)
    red = tracer.reduce()
    checks = check(cell, sweep, designs)
    print(f"window {window:.3f} s: {len(designs)} designs, xla compiles "
          f"{in_window['xla_compiles']} ({in_window['xla_compile_s']:.3f} s)",
          file=sys.stderr)
    times = np.array([d["t1"] - d["t0"] for d in designs])
    slow = designs[int(times.argmax())]
    print(f"design seconds: mean {times.mean():.4f}, sd {times.std():.4f}, "
          f"min {times.min():.4f}, max {times.max():.4f} (design "
          f"{int(times.argmax())}, passes "
          f"{ {k: round(v, 4) for k, v in slow['pass_times'].items()} }); full "
          f"collections {len(pauses)}, longest {max(pauses, default=0):.4f} s",
          file=sys.stderr)
    records = {"designs": [{k: v for k, v in d.items()
                            if k != "design"}
                           for d in designs],
               "trace": red, "tracer": (tracer.t0, tracer.t1),
               "in_window": in_window}
    return {"setup_s": setup_s, "e2e": e2e, "checks": checks,
            "attempted": len(designs), "failed": 0, "device": device,
            "trace": red, "records": records}


def check(cell: Cell, sweep: Sweep, designs: List[dict]) -> Dict[str, tuple]:
    """Over a sample of the window's designs drawn from the seed: output
    samples that differ from the application, and broken placement and
    routing rules; over every design of the window, the gap between the
    critical path the compiler reports and the benchmark's own timing of
    the same design."""
    tr = cell.traffic
    rng = np.random.default_rng([cell.seed, 1])
    picks = rng.choice(len(designs), min(tr["check_designs"], len(designs)),
                       replace=False)
    app = sweep.app
    src = cgra_ref.dfg_graph(app.build(app.unroll if app.sparse else 1))
    inputs = {k: rng.integers(0, 1 << 16, tr["check_cycles"])
              for k, nd in src[0].items() if nd.kind == "input"}
    bad = illegal = 0
    gap = max(abs(d["bench_cp_ns"] - d["cp_ns"]) for d in designs)
    for i in sorted(picks):
        d = designs[i]
        bad += cgra_ref.mismatches(src, cgra_ref.design_graph(d["design"]),
                                   inputs, tr["check_samples"], app.sparse)
        illegal += cgra_ref.illegal(d["design"], cell.config["fabric"])
    return {"output_mismatches": (bad, 0), "illegal": (illegal, 0),
            "cp_gap_ns": (gap, 0.0)}
