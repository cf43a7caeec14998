"""The zamba2.decode cell's own pieces at test sizes: the reference's
recurrence against a hand-written one, its readers on synthetic traces of
four chips, the batch and length that mfu.zamba2 recovers, and a whole
run of the cell at a tiny size through the harness (the CPU backend,
Pallas kernels in interpret mode)."""

import importlib.util
import os
import time

import numpy as np
import pytest

from bench.tests import util  # noqa: F401  (puts the repo on sys.path)
from bench.lib import flops as F
from bench.lib import harness as H
from bench.lib import program_spans as PS
from bench.lib import trace as T
from bench.lib import zamba2_flops as ZF

DIMS = H.load_json(os.path.join(H.BENCH, "configs", "zamba2-7b.json"))

#: the cell at a tiny size: 12 layers (shared blocks before 6 and 11),
#: 4 Mamba heads of 64 in 2 groups, 4 attention heads of 64
TINY = {"num_hidden_layers": 12, "hidden_size": 128, "n_mamba_heads": 4,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "num_query_groups": 4, "head_dim": 64, "attention_head_dim": 64,
        "attention_hidden_size": 256, "intermediate_size": 256,
        "ffn_hidden_size": 256, "vocab_size": 512}


def _metric(name):
    path = os.path.join(H.BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(
        ".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_ssm_scan_matches_a_hand_written_recurrence():
    """The reference's recurrence, heads reading their group's B and C,
    against a loop over time, heads and state entries in numpy."""
    import jax.numpy as jnp
    from bench.lib.zamba2_ref import ssm_scan
    rng = np.random.default_rng(0)
    s, nh, p, g, n = 7, 4, 3, 2, 5
    x = rng.normal(size=(s, nh, p))
    dt = rng.uniform(0.01, 0.5, size=(s, nh))
    a = -rng.uniform(1, 16, size=nh)
    Bg, Cg = rng.normal(size=(2, s, g, n))
    group = np.arange(nh) // (nh // g)
    y = np.asarray(ssm_scan(*(jnp.asarray(v, jnp.float32) for v in
                              (x, dt, a, Bg[:, group], Cg[:, group]))))
    want = np.zeros((s, nh, p))
    for h in range(nh):
        state = np.zeros((p, n))
        for t in range(s):
            for i in range(p):
                for j in range(n):
                    state[i, j] = (np.exp(dt[t, h] * a[h]) * state[i, j]
                                   + dt[t, h] * x[t, h, i]
                                   * Bg[t, group[h], j])
                want[t, h, i] = sum(state[i, j] * Cg[t, group[h], j]
                                    for j in range(n))
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,length", [(32, 513), (32, 2048), (1, 7)])
def test_batch_and_length_from_a_kernel_entry(b, length):
    kern = F.flash_decode_cost(b, DIMS["num_key_value_heads"], 1,
                               DIMS["head_dim"], [length] * b)
    assert ZF.batch_and_length(DIMS, kern) == (b, length)


def test_step_flops_of_a_prefill_come_from_the_next_step():
    kern = F.flash_decode_cost(32, 32, 1, 224, [513] * 32)
    work = [{"t": 0.0, "kernel": None}, {"t": 1.0, "kernel": kern}]
    assert ZF.step_flops(DIMS, work, 0) == ZF.prefill_flops(DIMS, 32, 512)
    assert ZF.step_flops(DIMS, work, 1) == ZF.decode_step_flops(DIMS, 32,
                                                                513)
    assert ZF.step_flops(DIMS, work[:1], 0) is None
    # per token, 2 x the weights it passes through: 81 Mamba layers
    # (6.35e9), 13 invocations of a 0.334e9 shared block with their own
    # adapter and linear (4.54e9), the tied head (0.115e9)
    per_token = ZF.decode_step_flops(DIMS, 1, 1) / 2
    assert 10.9e9 < per_token < 11.2e9


def _four_chip_trace(kernel_s, coll_s, step_s, steps):
    """A synthetic traced stretch on 4 chips: per step, a mamba op, a
    shared-block op holding ``kernel_s`` of flash_decode, an all-reduce of
    ``coll_s``, then idle to ``step_s``."""
    devices, stacks = {}, {}
    for c in range(4):
        evs, st = [], []
        for i in range(steps):
            t = i * step_s * 1e9
            for name, dur, stack in (
                    ("%fusion.1", 0.4 * step_s, "jit(d)/layers/while/body/"
                                                "mamba/ssd/dot"),
                    ("%flash_decode.3", kernel_s, "jit(d)/layers/while/body/"
                     "cond/branch_1_fun/shared_block/attention/pallas_call"),
                    ("%all-reduce.7", coll_s, "jit(d)/layers/while/body/"
                                              "mamba/dot_general")):
                evs.append((t, t + dur * 1e9, name))
                st.append(stack)
                t += dur * 1e9
        devices[f"/device:TPU:{c}"] = evs
        stacks[f"/device:TPU:{c}"] = st
    hi = steps * step_s * 1e9
    red = T.reduce_events(devices, [(0.0, hi, T.WINDOW_SPAN)])
    tr = PS.Trace((0.0, hi), [], {k: [(s, e, n, st) for (s, e, n), st in
                                      zip(evs, stacks[k])]
                                  for k, evs in devices.items()})
    return red, tr


def _records(red, step_s, steps, b=32, length=1024):
    kern = F.flash_decode_cost(b, 32, 1, 224, [length] * b)
    work = [{"t": (i + 0.5) * step_s, "flops": 0.0, "kernel": kern}
            for i in range(steps)]
    return {"trace": red, "work": work, "dims": DIMS,
            "tracer": (0.0, steps * step_s), "device_kind": "TPU v5 lite"}


def test_readers_on_a_four_chip_trace(monkeypatch):
    red, tr = _four_chip_trace(kernel_s=0.002, coll_s=0.001, step_s=0.01,
                               steps=5)
    rec = _records(red, 0.01, 5)
    from bench.lib import device_scopes
    monkeypatch.setattr(device_scopes, "trace_of", lambda records: tr)
    busy = 0.004 + 0.002 + 0.001
    read = lambda n: _metric(n).read(rec)
    assert read("ssm_share.zamba2") == pytest.approx(100 * 0.005 / busy)
    assert read("shared_block_share.zamba2") == pytest.approx(
        100 * 0.002 / busy)
    assert read("collective_share.zamba2") == pytest.approx(
        100 * 0.001 / busy)
    assert read("device_idle.zamba2") == pytest.approx(100 * 0.3)
    # without name stacks the scope shares read nothing
    bare = PS.Trace(tr.bounds, [], {k: [(s, e, n, None) for s, e, n, _ in v]
                                    for k, v in tr.devices.items()})
    monkeypatch.setattr(device_scopes, "trace_of", lambda records: bare)
    assert read("ssm_share.zamba2") is None
    # a loop outside ``layers``: a program compiled without the scopes
    # (served from a compile cache keyed without debug information)
    old = PS.Trace(tr.bounds, [], {k: [(s, e, n, st.replace("layers/", ""))
                                       for s, e, n, st in v]
                                   for k, v in tr.devices.items()})
    monkeypatch.setattr(device_scopes, "trace_of", lambda records: old)
    assert read("ssm_share.zamba2") is None
    assert read("collective_share.zamba2") is None


def test_roofline_and_mfu_are_per_chip(monkeypatch):
    """flash_decode at 80% of one chip's roofline for its 8 KV heads reads
    80%, where a reading of all 32 heads against one chip would read 320%;
    steps at a quarter of the four chips' peak read 25% MFU."""
    b, length, steps = 32, 1024, 5
    f, by = F.flash_decode_cost(b, 32, 1, 224, [length] * b)
    per_chip = F.roofline_seconds(f / 4, by / 4, 197e12, 819e9)[0]
    kernel_s = per_chip / 0.8
    step_s = 20 * kernel_s
    red, _ = _four_chip_trace(kernel_s, 0.0001, step_s, steps)
    rec = _records(red, step_s, steps, b, length)
    roof = _metric("flash_decode_roofline.zamba2").read(rec)
    assert roof == pytest.approx(80.0, rel=1e-6)
    whole = F.roofline_seconds(f, by, 197e12, 819e9)[0]
    assert 100 * whole / kernel_s > 100           # without the division
    # model FLOPs: a step's worth at 25% of four chips' peak
    flops = ZF.decode_step_flops(DIMS, b, length)
    rec["tracer"] = (0.0, flops / (0.25 * 4 * 197e12) * steps)
    rec["work"] = [dict(w, t=0.0) for w in rec["work"]]
    assert _metric("mfu.zamba2").read(rec) == pytest.approx(25.0)


def _tiny_cell(seed=5, seconds=0.5):
    bench = H.benchmark()
    cell = H.cell_from_benchmark(bench, "zamba2.decode", seed=seed,
                                 seconds=seconds, trace=False,
                                 t_process=time.perf_counter())
    cell.config.update(TINY)
    cell.traffic.update(shapes=[[4, 16, 32]], order=[0], check_requests=4,
                        check_tokens=128, trace_after_s=0.0,
                        trace_seconds=0.5)
    from bench.lib.meter import CompileMeter
    cell.meter = CompileMeter()
    return cell


def test_tiny_cell_runs_correct_and_control_fails(tmp_path):
    """The whole cell at a tiny size: the served tokens pass the limit,
    and the fp8 control's fail it."""
    from bench import control
    cell = _tiny_cell()
    cell.out_dir = str(tmp_path)
    r = control.reading(cell, [None])
    lim = cell.traffic["limits"]["share_gap_over_0.1"]
    assert r["served_tokens_short"] == 0
    assert r["share_gap_over_0.1"] <= lim < r["control_share_gap_over_0.1"]


def test_bf16_witness_lies_between_the_reference_and_the_control(capsys):
    """The reference with bfloat16 products moves the float32 logits by
    less than the fp8 control does; the control run reports its greedy
    choices' gaps and prints their statistics."""
    from bench.lib import zamba2_ref as R
    dims = {**DIMS, **TINY}
    w = R.make_weights(dims, 2615000003)
    seq = np.random.default_rng(0).integers(0, 512, 24)
    ref = np.asarray(R.logits(w, dims, seq))
    d16 = np.abs(np.asarray(R.logits(w, dims, seq, quant="bf16")) - ref)
    d8 = np.abs(np.asarray(R.logits(w, dims, seq, quant="fp8")) - ref)
    assert 0 < d16.max() < d8.max() and d16.mean() < d8.mean() / 4
    served = np.append(seq[8:], 0)       # teacher-forced on seq itself
    out = R.served_gaps(w, dims, seq[:8], served, control=True)
    rows = ref[7:]
    np.testing.assert_allclose(
        out["gaps"], rows.max(-1) - rows[np.arange(len(served)), served],
        rtol=1e-6)
    assert out["bf16_gaps"].shape == served.shape
    assert 0 <= out["bf16_gaps"].min() and \
        out["bf16_gaps"].mean() <= out["control_gaps"].mean()
    assert "bf16 reference in the program's place" in capsys.readouterr().err
