"""Run the main paths once on a TPU and check what comes out.

    python3 chip_smoke.py              # one chip: phases 1-3
    python3 chip_smoke.py --chips 4    # four chips: the multi-chip paths only

Phases on one chip:

1. compile: ``CascadeCompiler.compile`` with the jax placer and router
   (``pnr_backend="jax"``, ``verify=True``) for harris, mttkrp, thresh_conv
   and the granite-moe-1b-a400m block lowering, each at its paper-scale
   unroll on the full 32x16 fabric.  The jax STA refuses to run on a TPU
   (its emulated float64 does not round like the scalar oracle), so the
   compile times on the numpy STA, the smoke checks that the jax STA
   refuses, and says so on a line of its own.  Each design must verify,
   pass the router's legality checks, and time identically under the numpy
   STA and the scalar oracle; the jax placer's cost is shown beside a
   numpy-backend compile of the same app.
2. simulate: the compiled harris design for 16,384 cycles on the jax
   simulator, byte-equal to numpy over all cycles and to the interpreter
   over the first 1,024.
3. serve: granite-moe-1b-a400m at its published widths (random weights)
   through ``repro.launch.serve`` on a one-device mesh, 4 requests of 128
   prompt tokens and 32 greedy tokens, with and without the compiled Pallas
   flash kernels; logits finite, greedy tokens equal except where a
   bfloat16 near-tie lets them part (see ``compare_serves``).

With ``--chips 4``: the jax placer with its replica axis sharded across the
four chips against the same seed on one chip, and the granite serve with
4-way tensor parallelism against the same serve on one chip, compared as
in phase 3.

Every phase prints one JSON line of facts.  The last line is
``{"ok": true, "device": {...}}`` and is printed only when every phase
passed; any failure exits non-zero.  The script refuses to run anywhere but
on a TPU.  JAX's compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``.jax_cache/`` next to this file.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SIM_CYCLES = 16_384
SIM_INTERP_CYCLES = 1_024
SERVE_ARCH = "granite-moe-1b-a400m"
SERVE = dict(batch=4, prompt_len=128, gen=32)
#: a greedy choice within this many bf16 ulps of the runner-up is a tie:
#: one ulp for the rounding of the two logits, one for the sums before them
TIE_ULPS = 2


def emit(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}, default=str), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class CompileMeter:
    """XLA compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.secs = collections.Counter()
        self.events = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: self.secs.update({name: secs}))
        jax.monitoring.register_event_listener(
            lambda name, **kw: self.events.update([name]))

    def snapshot(self) -> dict:
        return {
            "xla_compile_s": self.secs["/jax/core/compile/backend_compile_duration"],
            "cache_hits": self.events["/jax/compilation_cache/cache_hits"],
            "cache_misses": self.events["/jax/compilation_cache/cache_misses"],
        }

    def since(self, before: dict) -> dict:
        now = self.snapshot()
        return {k: now[k] - before[k] for k in now}


# ---------------------------------------------------------------------------
# phase 1: compile on the device


def smoke_apps():
    from repro.configs import ARCHS
    from repro.core import ALL_APPS
    from repro.core.lmmap import lower_block
    return [ALL_APPS["harris"], ALL_APPS["mttkrp"], ALL_APPS["thresh_conv"],
            lower_block(ARCHS[SERVE_ARCH])]


def sparse_equivalent(app, design, n_tokens: int = 64) -> bool:
    """Ready-valid streams carry no latency: the routed design's token
    outputs must equal the source app's exactly."""
    import numpy as np
    from repro.core.sim import simulate_sparse
    ref = app.build(design.unroll_copies)
    rng = np.random.default_rng(0)
    ins = {n: rng.integers(0, 255, size=n_tokens).tolist()
           for n, nd in ref.nodes.items() if nd.kind == "input"}
    return (simulate_sparse(ref, ins, 100_000)
            == simulate_sparse(design.netlist.to_dfg(), ins, 100_000))


def jax_sta_refused(design, tm) -> str:
    """The jax STA's refusal on this device, or '' when it runs."""
    from repro.core.sta import analyze
    try:
        analyze(design, tm, backend="jax")
    except RuntimeError as e:
        return str(e)
    return ""


def phase_compile(meter: CompileMeter, apps=None) -> dict:
    from repro.core import CascadeCompiler, CompileCache, PassConfig
    from repro.core.route import check_legal
    from repro.core.sta import analyze

    designs = {}
    for app in apps or smoke_apps():
        before = meter.snapshot()
        jax_cfg = PassConfig.full(pnr_backend="jax", sta_backend="numpy")
        compiler = CascadeCompiler(cache=CompileCache(),
                                   stage_cache=CompileCache())
        t0 = time.perf_counter()
        res = compiler.compile(app, jax_cfg, verify=True)
        seconds = time.perf_counter() - t0
        xla = meter.since(before)

        ref = CascadeCompiler(cache=CompileCache(),
                              stage_cache=CompileCache()).compile(
            app, PassConfig.full(), verify=True)

        design, tm = res.design, compiler.timing
        verified = (res.pass_stats.get("verified") is True if not app.sparse
                    else sparse_equivalent(app, design))
        check_legal(design)
        refused = jax_sta_refused(design, tm)
        emit("sta_backend", app=app.name, jax_refused=refused,
             timed_on="numpy")
        check(bool(refused), f"{app.name}: the jax STA ran on the TPU")
        t0 = time.perf_counter()
        sta_np = analyze(design, tm, backend="numpy")
        sta_numpy_s = time.perf_counter() - t0
        sta_scalar = analyze(design, tm)
        fields = {f.name: getattr(sta_np, f.name) == getattr(sta_scalar, f.name)
                  for f in dataclasses.fields(sta_scalar)}
        place = res.pass_stats["pnr"]["place"]
        emit("compile", app=app.name, unroll=app.unroll,
             nodes=len(design.netlist.nodes), routes=len(design.routes),
             compile_s=seconds, **xla,
             pass_times=res.pass_stats.get("pass_times"),
             verified=verified, legal=True,
             sta_cp_ns=sta_np.critical_path_ns,
             sta_fmax_mhz=sta_np.max_freq_mhz, sta_numpy_s=sta_numpy_s,
             sta_equal=fields,
             jax_place_cost=place["best_cost"],
             jax_replica_costs=place.get("replica_costs"),
             numpy_place_cost=ref.pass_stats["pnr"]["place"]["best_cost"],
             numpy_compile_s=ref.compile_seconds,
             cp_ns=res.sta.critical_path_ns,
             numpy_cp_ns=ref.sta.critical_path_ns)
        check(verified, f"{app.name}: design failed verification")
        check(all(fields.values()),
              f"{app.name}: numpy STA differs from the scalar oracle in "
              f"{[k for k, v in fields.items() if not v]}")
        designs[app.name] = res
    return designs


# ---------------------------------------------------------------------------
# phase 2: simulate on the device


def phase_simulate(meter: CompileMeter, res, cycles: int = SIM_CYCLES,
                   interp_cycles: int = SIM_INTERP_CYCLES) -> None:
    import numpy as np
    from repro.core.sim import simulate

    g = res.design.netlist.to_dfg()
    rng = np.random.default_rng(0)
    ins = {n: rng.integers(0, 255, size=cycles).tolist()
           for n, nd in g.nodes.items() if nd.kind == "input"}
    before = meter.snapshot()
    t0 = time.perf_counter()
    out_jax = simulate(g, ins, cycles, backend="jax")
    jax_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out_jax_warm = simulate(g, ins, cycles, backend="jax")
    jax_warm_s = time.perf_counter() - t0
    xla = meter.since(before)
    t0 = time.perf_counter()
    out_np = simulate(g, ins, cycles, backend="numpy")
    np_s = time.perf_counter() - t0
    out_int = simulate(g, {k: v[:interp_cycles] for k, v in ins.items()},
                       interp_cycles)
    equal_np = out_jax == out_np and out_jax_warm == out_jax
    equal_int = all(out_jax[k][:interp_cycles] == v
                    for k, v in out_int.items())
    emit("simulate", app=res.app.name, nodes=len(g.nodes), cycles=cycles,
         outputs=len(out_jax), jax_s=jax_s, jax_warm_s=jax_warm_s,
         numpy_s=np_s, **xla, equal_numpy=equal_np,
         equal_interpreter_first=interp_cycles if equal_int else 0)
    check(equal_np, "jax simulation differs from numpy")
    check(equal_int, "jax simulation differs from the interpreter")


# ---------------------------------------------------------------------------
# phase 3: serve an LM


def serve_once(meter: CompileMeter, cfg, mesh, tag: str, **shape) -> dict:
    import numpy as np
    from repro.launch.serve import serve

    before = meter.snapshot()
    r = serve(cfg, mesh, **shape)
    logits = np.asarray(r["logits"])
    ids = np.asarray(r["ids"])
    want = (shape["gen"], shape["batch"], cfg.vocab_size)
    kernels = sum(c.as_text().count("tpu_custom_call")
                  for c in (r["prefill"], r["decode"]))
    emit("serve", run=tag, arch=cfg.name, mesh=dict(mesh.shape),
         use_flash=cfg.use_flash, **shape, compile_s=r["compile_s"],
         **meter.since(before), prefill_s=r["prefill_s"],
         decode_s=r["decode_s"],
         decode_tok_s=shape["batch"] * (shape["gen"] - 1) / r["decode_s"],
         logits_shape=list(logits.shape), finite=bool(np.isfinite(logits).all()),
         pallas_kernels=kernels, ids=ids.tolist())
    check(logits.shape == want, f"{tag}: logits {logits.shape} != {want}")
    check(bool(np.isfinite(logits).all()), f"{tag}: non-finite logits")
    check((kernels > 0) == cfg.use_flash,
          f"{tag}: {kernels} compiled Pallas kernels with "
          f"use_flash={cfg.use_flash}")
    r["ids"], r["logits"] = ids, logits
    return r


def bf16_ulp(x):
    """Spacing of bfloat16 numbers (7 fraction bits) at ``|x|``."""
    import numpy as np
    return np.exp2(np.floor(np.log2(np.abs(x) + np.finfo(np.float32).tiny))
                   - 7)


def compare_serves(ref, cand, runs) -> bool:
    """Whether ``cand`` chose ``ref``'s greedy tokens wherever the choice is
    decided by the model and not by rounding.

    The two runs compute the same logits with float sums in another order
    (another attention kernel, or partial sums reduced across chips).  The
    logits are bfloat16, and random weights give near-ties.  Up to a
    request's first differing token both runs have seen the same history,
    so that first difference is legitimate only where the reference's top
    two logits lie within ``TIE_ULPS`` bf16 ulps of each other: either
    token is then a greedy choice.  After it the histories differ and
    nothing more is compared.
    """
    import numpy as np

    same = ref["ids"] == cand["ids"]                       # [batch, gen]
    first = [int(np.argmin(row)) if not row.all() else None for row in same]
    top2 = np.sort(ref["logits"], axis=-1)[..., -2:]       # [gen, batch, 2]
    margin_ulps = (top2[..., 1] - top2[..., 0]) / bf16_ulp(top2[..., 1])
    # logit gap over the steps whose histories are still equal
    shared = [(s, r) for r, f in enumerate(first)
              for s in range(same.shape[1] if f is None else f + 1)]
    gap = [np.abs(cand["logits"][s, r] - ref["logits"][s, r]).max()
           for s, r in shared]
    at_div = [float(margin_ulps[f, r]) for r, f in enumerate(first)
              if f is not None]
    facts = {"tokens_equal": bool(same.all()), "first_divergence": first,
             "ref_margin_ulps_at_divergence": at_div,
             "tie_ulps": TIE_ULPS,
             "ref_near_tie_steps": int((margin_ulps <= TIE_ULPS).sum()),
             "shared_history_max_logit_gap": float(max(gap)),
             "shared_history_median_logit_gap": float(np.median(gap))}
    emit("serve_compare", runs=runs, **facts)
    return all(m <= TIE_ULPS for m in at_div)


def phase_serve(meter: CompileMeter, cfg=None, shape=None) -> dict:
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh_for

    cfg = cfg or get_config(SERVE_ARCH)
    shape = shape or SERVE
    mesh = make_mesh_for()
    plain = serve_once(meter, dataclasses.replace(cfg, use_flash=False),
                       mesh, "plain", **shape)
    flash_cfg = dataclasses.replace(cfg, use_flash=True)
    flash = serve_once(meter, flash_cfg, mesh, "flash", **shape)
    check(compare_serves(plain, flash, ["plain", "flash"]),
          "flash and plain greedy tokens part away from a near-tie")
    return plain


# ---------------------------------------------------------------------------
# --chips 4: the multi-chip paths and their one-chip references


def one_device(jax):
    """Patch ``jax.devices`` to the first device only: the one-chip
    reference of code that spreads over every visible device."""
    first = jax.devices()[:1]
    return mock.patch.object(jax, "devices", lambda *a, **kw: first)


def phase_place_sharded(meter: CompileMeter, app=None) -> None:
    import jax
    from repro.core import ALL_APPS, CascadeCompiler, CompileCache, PassConfig
    from repro.core.interconnect import Fabric
    from repro.core.place import IO_CAPACITY, TILE_CLASS, PlaceParams, place
    from repro.core.route import route

    app = app or ALL_APPS["harris"]
    fabric = Fabric()
    nl = CascadeCompiler(cache=CompileCache(),
                         stage_cache=CompileCache()).mapped_netlist(
        app, PassConfig.full())
    params = PlaceParams(alpha=1.6, seed=0, backend="jax")
    runs = {}
    for tag in ("sharded", "one_chip"):
        stats: dict = {}
        before = meter.snapshot()
        t0 = time.perf_counter()
        if tag == "sharded":
            placement = place(nl, fabric, params, stats=stats)
        else:
            with one_device(jax):
                placement = place(nl, fabric, params, stats=stats)
        seconds = time.perf_counter() - t0
        load = collections.Counter(placement.values())
        legal = all(fabric.tile_kind(placement[n]) == TILE_CLASS[nd.kind]
                    for n, nd in nl.nodes.items()) and all(
            k <= (IO_CAPACITY if fabric.tile_kind(t) == "io" else 1)
            for t, k in load.items())
        route(nl, placement, fabric)          # raises on an illegal route
        runs[tag] = stats
        emit("place", run=tag, app=app.name, nodes=len(nl.nodes),
             devices=stats["devices"], replicas=stats["replicas"],
             seconds=seconds, **meter.since(before), legal=legal,
             best_cost=stats["best_cost"],
             replica_costs=stats["replica_costs"])
        check(legal, f"{tag}: illegal placement")
    agree = runs["sharded"]["replica_costs"] == runs["one_chip"]["replica_costs"]
    emit("place_compare", replica_costs_equal=agree)
    check(runs["sharded"]["devices"] == 4, "replica axis was not sharded")


def phase_serve_tp(meter: CompileMeter, cfg=None, shape=None) -> None:
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh_for

    cfg = cfg or get_config(SERVE_ARCH)
    shape = shape or SERVE
    mesh = make_mesh_for()
    tp = serve_once(meter, cfg, mesh, "tp4", **shape)
    one = serve_once(meter, cfg, make_mesh_for(1), "one_chip", **shape)
    sharded = [k for k, s in tp["param_shardings"].items()
               if not s.is_fully_replicated]
    emit("tp_shardings", sharded_params=len(sharded),
         params=len(tp["param_shardings"]))
    check(sharded, "no parameter is sharded over the model axis")
    check(compare_serves(one, tp, ["one_chip", "tp4"]),
          "tp4 and one-chip greedy tokens part away from a near-tie")


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU (jax sees {device}); refusing to run",
              file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees {device}",
              file=sys.stderr)
        return 1

    from repro.launch.jax_cache import use_compile_cache
    cache_dir = use_compile_cache()
    emit("start", device=device, chips=args.chips, compile_cache=cache_dir)
    meter = CompileMeter()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_place_sharded(meter)
        phase_serve_tp(meter)
    else:
        designs = phase_compile(meter)
        phase_simulate(meter, designs["harris"])
        phase_serve(meter)
    emit("done", seconds=time.perf_counter() - t0, **meter.snapshot())
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
