"""The readers of the program's own spans and scopes, on a trace recorded
on the CPU (see record_program_trace.py), on one decode step cut from a
TPU trace (see cut_tpu_trace.py) and on hand-made events."""

import os
import shutil

import pytest

from bench.tests import util  # noqa: F401  (puts src on sys.path)
from bench.lib import harness as H
from bench.lib import program_spans as P

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "program_trace.xplane.pb")
NO_PROGRAM_SPANS = os.path.join(HERE, "data", "cpu_trace.xplane.pb")


def cpu(path):
    """On the CPU the ops run on the PjRt client's thread, which stands in
    for a device here."""
    return P.load(path, device_plane=lambda p: p == "/host:CPU",
                  ops_line=lambda p, line: line.startswith(
                      "tf_XLAPjRtCpuClient"))


def test_spans_and_attributes():
    tr = cpu(DATA)
    names = [sp[2] for sp in tr.spans]
    assert names.count("cascade.pass.place") == 4
    assert names.count("cascade.route.kernel") == 8
    kernel = next(sp for sp in tr.spans if sp[2] == "cascade.route.kernel")
    assert kernel[3] == {"T": 168, "D": 8, "S": 4}
    setup = next(sp for sp in tr.spans if sp[2] == "cascade.place.setup")
    assert setup[3] == {"replicas": 8, "nodes": 86, "K": 32}


def test_per_design_means_count_only_the_window():
    """Two of the four compiles lie in the window: 1 and 3 router
    iterations, so 2 a design; 3 timing runs each."""
    tr = cpu(DATA)
    assert P.per_pass(tr, "route", "cascade.route.iter", len) == 2
    assert P.per_pass(tr, "route", "cascade.route.kernel", len) == 2
    assert P.per_pass(tr, "post_pnr", "cascade.sta", len) == 3
    anneal = P.per_pass(tr, "place", "cascade.place.anneal", P.seconds)
    place = P.per_pass(tr, "place", "cascade.pass.place", P.seconds)
    assert 0 < anneal < place
    sta = P.per_pass(tr, "post_pnr", "cascade.sta", P.seconds)
    assert 2.5e-3 < sta < 3 * 2e-3 + 3e-3
    assert P.per_pass(tr, "route", "cascade.place.anneal", len) is None


def test_idle_labelled_by_innermost_program_span():
    tr = cpu(DATA)
    lo, hi = tr.bounds
    idle = P.idle_by_span(tr)
    busy = sum(e - s for s, e in P.T.union(
        [(max(s, lo), min(e, hi)) for s, e, _, _ in
         tr.devices["/host:CPU"] if e > lo and s < hi])) * 1e-9
    assert sum(idle.values()) + busy == pytest.approx((hi - lo) * 1e-9)
    for host in ("cascade.place.setup", "cascade.route.iter", "cascade.sta",
                 "cascade.pass.verify", P.NO_SPAN):
        assert idle[host] > 0.5e-3, host
    host = sum(v for k, v in idle.items()
               if k != P.NO_SPAN and k not in P.DEVICE_WAIT)
    assert P.host_idle_percent(tr) == pytest.approx(
        100 * host / ((hi - lo) * 1e-9))


def test_a_trace_without_program_spans_reads_none():
    tr = cpu(NO_PROGRAM_SPANS)
    assert tr.bounds is not None and not tr.spans
    assert P.per_pass(tr, "place", "cascade.place.anneal", P.seconds) is None
    assert P.host_idle_percent(tr) is None
    assert P.scope_share(tr, "attention") is None


def test_finds_only_this_runs_trace(tmp_path, monkeypatch):
    for cell, src in (("a", NO_PROGRAM_SPANS), ("b", DATA)):
        d = tmp_path / cell / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        shutil.copy(src, d / "x.xplane.pb")
    bounds = cpu(DATA).bounds
    monkeypatch.setattr(P, "TRACE_ROOT", str(tmp_path))
    assert P.find_trace({"trace": {"bounds_ns": bounds}}).endswith(
        os.path.join("b", "plugins", "profile", "t", "x.xplane.pb"))
    assert P.find_trace({"trace": {"bounds_ns": (0.0, 1.0)}}) is None
    assert P.find_trace({"trace": None}) is None
    records = {"trace": {"bounds_ns": bounds}}
    assert H.read_per_layer("route_iters", records) == 2
    assert H.read_per_layer("place_anneal_s", records) > 0
    assert H.read_per_layer("route_iters", {"trace": None}) is None


def hand_trace(ops):
    return P.Trace(bounds=(0, 100), spans=[],
                   devices={"/device:TPU:0": ops})


def test_scope_shares_from_name_stacks():
    """Window 0..100 ns, busy 0..90.  attention 0..30 and 20..40 (union
    40), moe 50..60, the layer loop's own ops 60..70 and 65..80 (union 20),
    the head 85..90; the while op around them counts only in busy."""
    stack = "jit(decode)/layers/while/body/"
    tr = hand_trace([
        (0, 90, "%while.4", "jit(decode)/layers/while"),
        (0, 30, "%fusion.1", stack + "attention/dot_general"),
        (20, 40, "%flash_decode.8", stack + "attention/pallas_call"),
        (50, 60, "%fusion.2", stack + "moe/dot_general"),
        (60, 70, "%copy.116", stack + "dynamic_slice"),
        (65, 80, "%fusion.3", "jit(decode)/layers/reshape"),
        (85, 90, "%fusion.4", "jit(decode)/head/dot_general"),
        (95, 120, "%fusion.5", "jit(decode)/layers/late"),
    ])
    secs = P.scope_seconds(tr)
    assert secs["busy"] == pytest.approx(95e-9)
    assert secs["attention"] == pytest.approx(40e-9)
    assert secs["moe"] == pytest.approx(10e-9)
    assert secs["layer_carry"] == pytest.approx(25e-9)
    assert P.scope_share(tr, "attention") == pytest.approx(100 * 40 / 95)
    assert P.scope_share(tr, "moe") == pytest.approx(100 * 10 / 95)


def test_scope_shares_need_the_layer_scope():
    """No name stacks (or a program without the scopes): no share."""
    assert P.scope_share(hand_trace([(0, 10, "%fusion.1", None)]),
                         "attention") is None
    assert P.scope_share(hand_trace(
        [(0, 10, "%fusion.1", "jit(decode)/while/body/dot_general")]),
        "moe") is None
    # a scoped decode step beside a prefill whose executable came from a
    # compile cache entry made before the scopes: its layer loop is a
    # while outside `layers`, and its time cannot be split
    assert P.scope_share(hand_trace([
        (0, 10, "%fusion.1", "jit(decode)/layers/while/body/attention/dot"),
        (10, 20, "%fusion.2", "jit(prefill)/while/body/closed_call/dot"),
    ]), "attention") is None


def test_name_stacks_of_a_tpu_decode_step():
    """One decode step cut from a granite.decode trace taken on a TPU v5e
    (see cut_tpu_trace.py): the ops' name stacks come from their metadata's
    ``tf_op`` stat, and the scopes split the step's device time."""
    tr = P.load(os.path.join(HERE, "data", "tpu_decode_step.xplane.pb"))
    ops, = tr.devices.values()
    assert len(ops) > 1000
    assert sum(1 for op in ops if op[3]) > 0.95 * len(ops)
    kernel = [op for op in ops if op[2].startswith("%flash_decode")]
    assert kernel and all("attention" in P.scope_of(op[3])
                          for op in kernel)
    assert any(P.CONTAINER.match(op[2]) for op in ops)
    secs = P.scope_seconds(tr)
    assert min(secs["attention"], secs["layer_carry"]) > secs["moe"] > 0
    parts = sum(P.scope_share(tr, k)
                for k in ("attention", "moe", "layer_carry"))
    assert 50 < parts <= 100
