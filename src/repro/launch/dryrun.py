import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import/initialization: jax locks the device count on
# first backend init.  This module is the ONLY place the 512 placeholder
# devices exist — tests and benches see the default single device.

"""Multi-pod dry-run: prove every (architecture x shape x mesh) cell lowers,
SPMD-partitions, and compiles on the production mesh, and extract the
roofline terms from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
        --shape train_4k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all

Per cell this records (experiments/dryrun/<cell>.json):
  * memory_analysis        — per-device bytes (args/output/temp/peak)
  * cost_analysis          — per-device HLO FLOPs + bytes accessed
  * collective bytes       — wire bytes per device, parsed from the
                             partitioned HLO (all-gather / all-reduce /
                             reduce-scatter / all-to-all / collective-permute)
  * roofline terms         — compute / memory / collective seconds + the
                             dominant term (TPU v5e peaks from
                             ``repro.distributed.peaks``)
"""

import argparse
import dataclasses
import json
import re
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ARCHS, SHAPES, cell_is_runnable, get_config,
                           model_flops)
from repro.data.pipeline import batch_specs
from repro.distributed import sharding as shd
from repro.distributed.peaks import TPU_V5E, peaks_for
from repro.launch import steps as S
from repro.launch.mesh import make_production_mesh
from repro.models import LM

# ---------------------------------------------------------------------------
# the production mesh is a v5e pod: its rooflines use v5e peaks

PEAK = peaks_for(TPU_V5E)

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "c64": 8, "f32": 4, "s32": 4,
                "u32": 4, "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(m: re.Match) -> int:
    dt, dims = m.group(1), m.group(2)
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def _wire_factor(op: str, n: int) -> float:
    """Per-device wire bytes as a multiple of the result-shape bytes for a
    ring implementation with n participants."""
    if n <= 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * (n - 1) / n
    if op == "all-gather":
        return (n - 1) / n                   # result is the gathered tensor
    if op == "reduce-scatter":
        return float(n - 1)                  # result is the 1/n shard
    if op == "all-to-all":
        return (n - 1) / n
    return 1.0                               # collective-permute


def parse_collectives(hlo_text: str) -> Dict[str, Any]:
    """Sum per-device wire bytes of every collective in partitioned HLO."""
    per_op: Dict[str, float] = {c: 0.0 for c in _COLLECTIVES}
    counts: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        if " = " not in ls:
            continue
        rhs = ls.split(" = ", 1)[1]
        opname = None
        for c in _COLLECTIVES:
            # matches "bf16[...] all-gather(..." and async "-start" forms
            if f" {c}(" in f" {rhs}" or f" {c}-start(" in f" {rhs}":
                opname = c
                break
        if opname is None:
            continue
        # participants
        n = 1
        g = _GROUPS_RE.search(rhs)
        if g:
            n = g.group(1).count(",") + 1
        else:
            gi = _GROUPS_IOTA_RE.search(rhs)
            if gi:
                n = int(gi.group(2))
        # result bytes: all dtype[...] before the op call
        head = rhs.split(f"{opname}-start(")[0] if f"{opname}-start(" in rhs \
            else rhs.split(f"{opname}(")[0]
        rbytes = sum(_shape_bytes(m) for m in _SHAPE_RE.finditer(head))
        per_op[opname] += _wire_factor(opname, n) * rbytes
        counts[opname] += 1
    total = sum(per_op.values())
    return {"bytes_per_device": total,
            "per_op_bytes": per_op, "per_op_counts": counts}


# ---------------------------------------------------------------------------


def _mem_dict(compiled) -> Dict[str, float]:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:           # backend without memory analysis
        return {"error": str(e)}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes", "peak_memory_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    args = out.get("argument_size_in_bytes", 0)
    alias = out.get("alias_size_in_bytes", 0)
    out["resident_bytes_per_device"] = (
        args - alias + out.get("output_size_in_bytes", 0)
        + out.get("temp_size_in_bytes", 0))
    return out


def _cost_dict(compiled) -> Dict[str, float]:
    try:
        ca = compiled.cost_analysis()
    except Exception as e:
        return {"error": str(e)}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float)) and k in
            ("flops", "bytes accessed", "transcendentals",
             "utilization operand 0 {}", "bytes accessed output {}")}


def roofline_terms(flops: float, hbm_bytes: float, coll_bytes: float
                   ) -> Dict[str, Any]:
    t_c = flops / PEAK.flops
    t_m = hbm_bytes / PEAK.hbm_bw
    t_x = coll_bytes / PEAK.ici_bw
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
              key=lambda kv: kv[1])
    return {"compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
            "bound": dom[0],
            "step_time_lower_bound_s": max(t_c, t_m, t_x)}


# ---------------------------------------------------------------------------
# cell construction


def build_cell(cfg, shape, multi_pod: bool):
    """Returns (mesh, jitted fn, SDS args) for the cell.

    NOTE: sharding specs are resolved against the ACTIVE mesh (axis
    presence + divisibility checks), so everything is built under
    ``jax.sharding.set_mesh(mesh)`` — resolving outside would silently
    replicate."""
    model = LM(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    shd.set_rules(S.rules_for(cfg))

    with jax.sharding.set_mesh(mesh):
        repl = NamedSharding(mesh, P())

        def logits_sh(batch, vocab):
            spec = shd.resolve_spec(("batch", "vocab"), dims=(batch, vocab))
            return NamedSharding(mesh, spec)

        if shape.kind == "train":
            opt_cfg = S.make_optimizer_config(cfg)
            st_sh, b_sh = S.train_shardings(model, opt_cfg, mesh, shape)
            gspecs = jax.tree.map(lambda s: s.spec, st_sh["params"])
            fn = S.make_train_step(model, opt_cfg, grad_specs=gspecs)
            args = (S.train_state_shapes(model, opt_cfg),
                    batch_specs(cfg, shape))
            in_shardings = (st_sh, b_sh)
            out_shardings = (st_sh, repl)
            donate = (0,)                 # state buffers alias in->out
        elif shape.kind == "prefill":
            fn = S.make_prefill_step(model)
            p_sh, b_sh, c_sh = S.serve_shardings(model, mesh, shape)
            args = (model.shapes(), batch_specs(cfg, shape),
                    model.cache_shapes(shape.global_batch, shape.seq_len))
            in_shardings = (p_sh, b_sh, c_sh)
            out_shardings = (logits_sh(shape.global_batch, cfg.padded_vocab),
                             c_sh)
            donate = (2,)                 # cache
        else:  # decode
            fn = S.make_decode_step(model)
            p_sh, b_sh, c_sh = S.serve_shardings(model, mesh, shape)
            args = (model.shapes(), batch_specs(cfg, shape),
                    model.cache_shapes(shape.global_batch, shape.seq_len),
                    jax.ShapeDtypeStruct((), jnp.int32))
            in_shardings = (p_sh, b_sh, c_sh, repl)
            out_shardings = (logits_sh(shape.global_batch, cfg.padded_vocab),
                             c_sh)
            donate = (2,)
        jitted = jax.jit(fn, in_shardings=in_shardings,
                         out_shardings=out_shardings, donate_argnums=donate)
    return mesh, jitted, args


def _lower_compile(cfg, shape, multi_pod):
    mesh, jitted, args = build_cell(cfg, shape, multi_pod)
    t0 = time.time()
    with jax.sharding.set_mesh(mesh):
        lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    return mesh, compiled, round(t_lower, 2), round(time.time() - t0, 2)


def exact_arg_bytes(cfg, shape, multi_pod) -> int:
    """Analytic per-device input bytes from the NamedShardings (exact;
    XLA-CPU's memory_analysis argument size cross-check)."""
    import numpy as np
    model = LM(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    shd.set_rules(S.rules_for(cfg))
    with jax.sharding.set_mesh(mesh):
        if shape.kind == "train":
            opt_cfg = S.make_optimizer_config(cfg)
            shardings, b_sh = S.train_shardings(model, opt_cfg, mesh, shape)
            shapes_tree = (S.train_state_shapes(model, opt_cfg),
                           batch_specs(cfg, shape))
            sh_tree = (shardings, b_sh)
        else:
            p_sh, b_sh, c_sh = S.serve_shardings(model, mesh, shape)
            shapes_tree = (model.shapes(), batch_specs(cfg, shape),
                           model.cache_shapes(shape.global_batch,
                                              shape.seq_len))
            sh_tree = (p_sh, b_sh, c_sh)
    total = 0
    for sds, sh in zip(jax.tree.leaves(shapes_tree),
                       jax.tree.leaves(sh_tree)):
        total += int(np.prod(sh.shard_shape(sds.shape))) * sds.dtype.itemsize
    return total


# ---------------------------------------------------------------------------
# cost probes: unrolled reduced-depth modules with trip-count-exact counts
#
# XLA's cost analysis counts a while (scan/map) body ONCE, so the scanned
# full-depth module under-reports FLOPs/bytes/collectives.  The probes lower
# the same step with `scan_layers=False` (python-unrolled layers) and einsum
# attention (loop-free) at 1 and 2 structural units of depth; every count is
# then extrapolated linearly: total(L) = c1 + (L/u - 1) * (c2 - c1).
# Attention score traffic is afterwards corrected from "materialized f32
# scores" (what the einsum probe does) to "streamed blocks" (what the real
# blockwise/flash impl does) — see _attn_traffic_correction.


def probe_unit(cfg) -> int:
    """Structural unit: smallest layer group the architecture repeats."""
    if cfg.family == "moe":
        return cfg.moe_layer_period
    if cfg.family == "hybrid":
        # a period k when the shared block runs before every k-th layer,
        # else the whole irregular stack
        ids = cfg.hybrid_layers
        k = ids[1] - ids[0] if len(ids) > 1 else 0
        regular = k and ids == tuple(range(k - 1, cfg.num_layers, k))
        return k if regular else cfg.num_layers
    if cfg.family == "vlm":
        return cfg.cross_attn_every or 1
    return 1


def make_probe_cfg(cfg, units: int):
    u = probe_unit(cfg)
    kw = dict(num_layers=u * units, scan_layers=False, attn_impl="einsum")
    if cfg.family == "audio":
        kw["encoder_layers"] = max(
            1, cfg.encoder_layers * u * units // cfg.num_layers)
    return cfg.replace(**kw)


def _extrapolate(c1: float, c2: float, n_units: int) -> float:
    return c1 + (n_units - 1) * (c2 - c1)


def run_probes(cfg, shape, multi_pod: bool) -> Dict[str, Any]:
    u = probe_unit(cfg)
    n_units = cfg.num_layers // u
    res = []
    for units in (1, 2):
        pcfg = make_probe_cfg(cfg, units)
        _, compiled, _, t_c = _lower_compile(pcfg, shape, multi_pod)
        cost = _cost_dict(compiled)
        coll = parse_collectives(compiled.as_text())
        res.append({"cost": cost, "coll": coll, "compile_s": t_c})
    out: Dict[str, Any] = {"unit_layers": u, "units": n_units,
                           "probe_compile_s": [r["compile_s"] for r in res]}
    for key in ("flops", "bytes accessed", "transcendentals"):
        c1 = res[0]["cost"].get(key, 0.0)
        c2 = res[1]["cost"].get(key, 0.0)
        out[key] = _extrapolate(c1, c2, n_units)
    out["collective_bytes_per_device"] = _extrapolate(
        res[0]["coll"]["bytes_per_device"],
        res[1]["coll"]["bytes_per_device"], n_units)
    out["collective_per_op"] = {
        op: _extrapolate(res[0]["coll"]["per_op_bytes"][op],
                         res[1]["coll"]["per_op_bytes"][op], n_units)
        for op in _COLLECTIVES}
    out["collective_counts_unit"] = {
        op: res[1]["coll"]["per_op_counts"][op]
        - res[0]["coll"]["per_op_counts"][op] for op in _COLLECTIVES}
    return out


def _attn_traffic_correction(cfg, shape, n_model: int, n_batch: int
                             ) -> Dict[str, float]:
    """Per-device HBM-byte delta: einsum-probe score materialization ->
    streamed blockwise attention (the impl the full compile actually uses
    for q-length >= 4096).  Returns {"subtract": ..., "add": ...}."""
    s = shape.seq_len
    if shape.kind == "decode" or s < 4096 or cfg.family == "ssm":
        return {"subtract": 0.0, "add": 0.0}
    b_loc = max(1, shape.global_batch // n_batch)
    hq = cfg.num_heads
    hq_loc = hq // n_model if hq % n_model == 0 else hq
    hkv = cfg.num_kv_heads
    hkv_loc = hkv // n_model if hkv % n_model == 0 else hkv
    hd = cfg.resolved_head_dim

    # how many self-attention layers at this q-length?
    if cfg.family == "hybrid":
        n_attn = len(cfg.hybrid_layers)
    elif cfg.family in ("dense", "moe", "vlm", "audio"):
        n_attn = cfg.num_layers
    else:
        n_attn = 0

    # score-tensor passes: fwd write+read (softmax) + prob write+read = 4;
    # training adds remat re-forward (4) and backward dS/dP traffic (8)
    passes = 16.0 if shape.kind == "train" else 4.0
    score_bytes = b_loc * hq_loc * float(s) * float(s) * 4.0
    subtract = n_attn * passes * score_bytes
    # streamed impl re-reads K/V once per 512-row q block
    n_qb = max(1, s // 512)
    kv_bytes = b_loc * float(s) * hkv_loc * hd * 2.0 * 2.0     # K and V, bf16
    add = n_attn * (3.0 if shape.kind == "train" else 1.0) * n_qb * kv_bytes
    return {"subtract": subtract, "add": add}


# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             out_dir: Optional[str] = "experiments/dryrun",
             full: bool = True, probes: bool = True,
             cfg_override=None, tag: str = "") -> Dict[str, Any]:
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if tag:
        cell["tag"] = tag
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        cell["skipped"] = why
        return _emit(cell, out_dir)

    n_dev = 512 if multi_pod else 256
    n_model = 16
    n_batch = n_dev // n_model

    if full:
        mesh, compiled, t_lower, t_compile = _lower_compile(
            cfg, shape, multi_pod)
        cell["lower_s"] = t_lower
        cell["compile_s"] = t_compile
        cell["devices"] = mesh.size
        cell["memory"] = _mem_dict(compiled)
        cell["memory"]["args_bytes_exact"] = exact_arg_bytes(
            cfg, shape, multi_pod)
        cell["cost_scanned_raw"] = _cost_dict(compiled)

    if probes:
        pr = run_probes(cfg, shape, multi_pod)
        cell["probe"] = pr
        flops = pr.get("flops", 0.0)
        hbm = pr.get("bytes accessed", 0.0)
        corr = _attn_traffic_correction(cfg, shape, n_model, n_batch)
        cell["attn_traffic_correction"] = corr
        hbm_corr = max(0.0, hbm - corr["subtract"]) + corr["add"]
        coll = pr.get("collective_bytes_per_device", 0.0)
        cell["roofline"] = roofline_terms(flops, hbm_corr, coll)
        cell["roofline"]["memory_s_uncorrected"] = hbm / PEAK.hbm_bw
        mf = model_flops(cfg, shape)
        cell["model_flops_total"] = mf
        cell["model_flops_per_device"] = mf / n_dev
        if flops:
            cell["useful_flop_ratio"] = round(mf / n_dev / flops, 4)
            cell["roofline_fraction"] = round(
                (mf / n_dev / PEAK.flops) /
                cell["roofline"]["step_time_lower_bound_s"], 4)
    return _emit(cell, out_dir)


def _emit(cell: Dict[str, Any], out_dir: Optional[str]) -> Dict[str, Any]:
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"_{cell['tag']}" if cell.get("tag") else ""
        name = f"{cell['arch']}_{cell['shape']}_{cell['mesh']}{suffix}.json"
        with open(os.path.join(out_dir, name), "w") as f:
            json.dump(cell, f, indent=1, default=float)
    status = "SKIP" if "skipped" in cell else \
        cell.get("roofline", {}).get("bound", "?")
    print(f"[dryrun] {cell['arch']} x {cell['shape']} x {cell['mesh']}: "
          f"{status} "
          f"(compile {cell.get('compile_s', '-')}s)", flush=True)
    return cell


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-full", action="store_true",
                    help="skip the full-depth feasibility compile")
    ap.add_argument("--no-probes", action="store_true",
                    help="skip the cost probes (feasibility only)")
    args = ap.parse_args()

    if args.all:
        failures = []
        for arch in ARCHS:
            for shape in SHAPES:
                for mp in (False, True):
                    try:
                        # roofline probes are a single-pod deliverable;
                        # multi-pod proves the "pod" axis shards (full only)
                        run_cell(arch, shape, mp, args.out,
                                 full=not args.no_full,
                                 probes=not (args.no_probes or mp))
                    except Exception as e:
                        failures.append((arch, shape, mp, repr(e)[:200]))
                        print(f"[dryrun] FAIL {arch} x {shape} x "
                              f"{'2x16x16' if mp else '16x16'}: {e!r}",
                              flush=True)
        print(f"[dryrun] sweep done, {len(failures)} failures")
        for f in failures:
            print("   ", f)
        return
    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    run_cell(args.arch, args.shape, args.multi_pod, args.out,
             full=not args.no_full, probes=not args.no_probes)


if __name__ == "__main__":
    main()
