"""The numpy STA engine (``repro.core.sta_vec``) against the scalar oracle.

Bit-identity of the scalar oracle with the lowered numpy engine —
critical path ns, path reconstruction, arrival maps, segment counts — on
every app's routed design and on randomized register states (hypothesis,
via the ``_hypothesis_compat`` shim); byte-identity of the incremental
post-PnR pipelining loop across engines (histories, stop reasons,
register placements) including the budget, round-hook, and power-cap
stop paths; the ``(driver, sink)`` route index vs the reference scan;
the refusal of unknown engine names; and the ``sta_backend`` stage-key
seam.
"""

import copy
import pickle
import random

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import (ALL_APPS, CascadeCompiler, CompileCache, PassConfig,
                        PostPnRParams, analyze, analyze_vec, lower_design,
                        post_pnr_pipeline, power_capped_pipeline, stage_key,
                        STA_BACKENDS)
from repro.core.passes import DEFAULT_SCHEDULE
from repro.core.post_pnr import _find_branch
from repro.core.sta_vec import IncrementalSTA

#: the lowered engine under test beside the scalar oracle
VEC_BACKENDS = ("numpy",)

#: (app, unroll) design points — every app: dense, sparse and predicated,
#: with mttkrp unrolled
APPS = tuple((name, 2 if name == "mttkrp" else 1) for name in ALL_APPS)

_COMPILER = None
_ROUTED = {}


def _compiler():
    global _COMPILER
    if _COMPILER is None:
        _COMPILER = CascadeCompiler(cache=CompileCache())
    return _COMPILER


def _routed(name, unroll):
    """(design, timing-model) for a routed (pre-pipelining) compile; the
    cached master copy is never mutated — tests deepcopy it."""
    key = (name, unroll)
    if key not in _ROUTED:
        c = _compiler()
        art = c.compile_to_stage(ALL_APPS[name], PassConfig(),
                                 stage="routed", unroll=unroll)
        _ROUTED[key] = (art.state["design"], art.state["place_timing"])
    return _ROUTED[key]


def _assert_reports_identical(ref, got):
    """Exact — not approximate — equality on every report field."""
    assert got.critical_path_ns == ref.critical_path_ns
    assert got.max_freq_mhz == ref.max_freq_mhz
    assert got.clock_period_ns == ref.clock_period_ns
    assert got.n_segments == ref.n_segments
    assert got.critical_path == ref.critical_path
    assert got.arrival_out == ref.arrival_out


def _reg_state(design):
    return ({k: sorted(rb.reg_hops) for k, rb in design.routes.items()},
            {b.key: b.n_regs for b in design.netlist.branches})


# ---------------------------------------------------------------------------
# one-shot bit-identity: scalar oracle vs lowered engines
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("app,unroll", APPS)
@pytest.mark.parametrize("backend", VEC_BACKENDS)
def test_backends_bit_identical_on_routed_designs(app, unroll, backend):
    design, tm = _routed(app, unroll)
    ref = analyze(design, tm)
    _assert_reports_identical(ref, analyze(design, tm, backend=backend))
    # the sta_vec entry point and the analyze() dispatch agree too
    _assert_reports_identical(ref, analyze_vec(design, tm))


@pytest.mark.parametrize("backend", VEC_BACKENDS)
def test_backends_bit_identical_after_pipelining(backend):
    design, tm = _routed("harris", 1)
    d = copy.deepcopy(design)
    post_pnr_pipeline(d, tm, PostPnRParams(max_iters=8))
    _assert_reports_identical(analyze(d, tm), analyze(d, tm, backend=backend))


@pytest.mark.parametrize("backend", VEC_BACKENDS)
def test_clock_granularity_quantization_matches(backend):
    design, tm = _routed("gaussian", 1)
    ref = analyze(design, tm, clock_granularity_ns=0.25)
    got = analyze(design, tm, backend=backend, clock_granularity_ns=0.25)
    _assert_reports_identical(ref, got)


def test_sampled_delay_path_stays_on_scalar_walk():
    """``rng`` draws one factor per instance in scalar visit order — the
    dispatch must route sampled analyses to the oracle regardless of the
    requested backend."""
    design, tm = _routed("gaussian", 1)
    a = analyze(design, tm, rng=np.random.default_rng(7))
    b = analyze(design, tm, rng=np.random.default_rng(7), backend="numpy")
    _assert_reports_identical(a, b)
    assert a.critical_path_ns != analyze(design, tm).critical_path_ns


def test_unknown_vec_backend_rejected():
    """Both entry points refuse a name outside ``STA_BACKENDS`` — the
    one-shot ``analyze`` (also on its sampled-delay path) and the
    pipelining loop's engine — and so does a compile configured with it."""
    assert STA_BACKENDS == ("scalar", "numpy")
    design, tm = _routed("gaussian", 1)
    for backend in ("jax", "torch"):
        with pytest.raises(ValueError, match="unknown STA backend"):
            analyze(design, tm, backend=backend)
        with pytest.raises(ValueError, match="'scalar', 'numpy'"):
            analyze(design, tm, backend=backend,
                    rng=np.random.default_rng(0))
        with pytest.raises(ValueError, match="unknown STA backend"):
            post_pnr_pipeline(copy.deepcopy(design), tm,
                              sta_backend=backend)
    with pytest.raises(ValueError, match="unknown STA backend"):
        _compiler().compile(ALL_APPS["gaussian"],
                            PassConfig(sta_backend="jax"))


# ---------------------------------------------------------------------------
# randomized register states (property suite)
# ---------------------------------------------------------------------------


def _random_reg_state(design, seed):
    """Scatter registers over free hop sites of a deepcopied design."""
    d = copy.deepcopy(design)
    rng = random.Random(seed)
    for rb in d.routes.values():
        for i in range(len(rb.hops)):
            if rng.random() < 0.3:
                rb.reg_hops.add(i)
        rb.branch.n_regs = len(rb.reg_hops)
    return d


def _check_random_reg_state(app_idx, seed):
    name, unroll = APPS[app_idx]
    design, tm = _routed(name, unroll)
    d = _random_reg_state(design, seed)
    ref = analyze(d, tm)
    for backend in VEC_BACKENDS:
        _assert_reports_identical(ref, analyze(d, tm, backend=backend))


def _check_per_seed_determinism(seed):
    design, tm = _routed("gaussian", 1)
    d = _random_reg_state(design, seed)
    for backend in ("scalar",) + VEC_BACKENDS:
        a = analyze(d, tm, backend=backend)
        b = analyze(d, tm, backend=backend)
        _assert_reports_identical(a, b)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, len(APPS) - 1), st.integers(0, 2**31 - 1))
def test_random_reg_states_bit_identical(app_idx, seed):
    _check_random_reg_state(app_idx, seed)


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_per_seed_determinism(seed):
    _check_per_seed_determinism(seed)


def test_random_reg_states_seeded_sweep():
    """The same properties under a fixed seeded sweep, so the randomized
    coverage runs even where hypothesis is not installed."""
    rng = random.Random(0xCA5CADE)
    for app_idx in range(len(APPS)):
        for _ in range(4):
            _check_random_reg_state(app_idx, rng.getrandbits(31))
    for _ in range(3):
        _check_per_seed_determinism(rng.getrandbits(31))


# ---------------------------------------------------------------------------
# the incremental engine: dirty-cone re-propagation == fresh oracle walk
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", VEC_BACKENDS)
def test_incremental_engine_tracks_mutations(backend):
    design, tm = _routed("harris", 1)
    d = copy.deepcopy(design)
    eng = IncrementalSTA(d, tm)
    rng = random.Random(11)
    added = []
    for rb in d.routes.values():
        for i in range(len(rb.hops)):
            if i not in rb.reg_hops and rng.random() < 0.1:
                rb.reg_hops.add(i)
                added.append((rb.branch.key, i))
    eng.notify_added(added)
    _assert_reports_identical(analyze(d, tm),
                              eng.analyze(with_arrivals=True))
    # remove a few again; the cone must shrink back bit-identically
    removed = added[::3]
    for bkey, i in removed:
        d.routes[bkey].reg_hops.discard(i)
    eng.notify_removed(removed)
    _assert_reports_identical(analyze(d, tm),
                              eng.analyze(with_arrivals=True))
    # resync from the design after an external edit
    for rb in d.routes.values():
        if rb.hops:
            rb.reg_hops.add(0)
    eng.resync()
    _assert_reports_identical(analyze(d, tm),
                              eng.analyze(with_arrivals=True))


def test_lowering_is_shared_and_picklable():
    design, tm = _routed("gaussian", 1)
    L = lower_design(design, tm)
    ref = analyze(design, tm)
    # one lowering serves a deepcopied fork (structure is shared)
    fork = copy.deepcopy(design)
    _assert_reports_identical(ref, analyze_vec(fork, tm, lowering=L))
    # pickles (the scalar mirrors are dropped), still exact
    L2 = pickle.loads(pickle.dumps(L))
    _assert_reports_identical(ref, analyze_vec(design, tm, lowering=L2))


# ---------------------------------------------------------------------------
# the pipelining loop: byte-identical across engines, every stop path
# ---------------------------------------------------------------------------


def _loop_state(design, tm, res):
    return (res.history, res.stop_reason, res.iterations, res.initial_ns,
            res.final_ns, res.registers_added, _reg_state(design))


@pytest.mark.parametrize("app,unroll", APPS)
def test_post_pnr_loop_byte_identical_across_backends(app, unroll):
    design, tm = _routed(app, unroll)
    d0 = copy.deepcopy(design)
    ref = post_pnr_pipeline(d0, tm, PostPnRParams(max_iters=40))
    # the engine-maintained report matches a fresh oracle walk of the
    # final design (pins the _RoundDelta undo bookkeeping)
    assert analyze(d0, tm).critical_path_ns == ref.final_ns
    for backend in VEC_BACKENDS:
        d = copy.deepcopy(design)
        res = post_pnr_pipeline(d, tm, PostPnRParams(max_iters=40),
                                sta_backend=backend)
        assert _loop_state(d, tm, res) == _loop_state(d0, tm, ref)


@pytest.mark.parametrize("backend", VEC_BACKENDS)
def test_register_budget_stop_byte_identical(backend):
    design, tm = _routed("harris", 1)
    params = PostPnRParams(max_iters=40, register_budget=2)
    d0 = copy.deepcopy(design)
    ref = post_pnr_pipeline(d0, tm, params)
    d = copy.deepcopy(design)
    res = post_pnr_pipeline(d, tm, params, sta_backend=backend)
    assert _loop_state(d, tm, res) == _loop_state(d0, tm, ref)
    assert analyze(d, tm).critical_path_ns == res.final_ns


@pytest.mark.parametrize("backend", VEC_BACKENDS)
def test_round_hook_stop_byte_identical(backend):
    design, tm = _routed("harris", 1)

    def run(sta):
        d = copy.deepcopy(design)
        calls = []

        def hook(dd, rep):
            calls.append(rep.critical_path_ns)
            return len(calls) < 2        # reject the second round

        res = post_pnr_pipeline(d, tm, PostPnRParams(max_iters=40),
                                round_hook=hook, sta_backend=sta)
        return _loop_state(d, tm, res), calls

    ref_state, ref_calls = run("scalar")
    got_state, got_calls = run(backend)
    assert ref_state[1] == "round_hook"
    assert got_state == ref_state
    assert got_calls == ref_calls


@pytest.mark.parametrize("backend", VEC_BACKENDS)
def test_power_cap_stop_byte_identical(backend):
    design, tm = _routed("harris", 1)
    c = _compiler()
    iters = ALL_APPS["harris"].iterations

    def run(sta, cap):
        d = copy.deepcopy(design)
        pc = power_capped_pipeline(d, tm, c.energy, iters, cap_mw=cap,
                                   sta_backend=sta)
        pts = [(p.round, p.critical_path_ns, p.freq_mhz, p.power_mw,
                p.edp_js, p.registers_added) for p in pc.trajectory]
        return (pts, pc.stop_reason, pc.rounds_rolled_back, pc.feasible,
                _loop_state(d, tm, pc.post_pnr))

    ref0 = run("scalar", None)
    powers = [p[3] for p in ref0[0]]
    assert powers[-1] > powers[0], "no power spread; cap test is vacuous"
    cap = (powers[0] + powers[-1]) / 2.0   # forces a mid-loop rollback
    ref = run("scalar", cap)
    assert ref[2] == 1                    # exactly one round rolled back
    assert run(backend, cap) == ref
    assert run(backend, None) == ref0


# ---------------------------------------------------------------------------
# (driver, sink) -> branch-key index vs the reference scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("app,unroll", APPS)
def test_branch_index_agrees_with_scan(app, unroll):
    design, _ = _routed(app, unroll)
    pairs = {(k[0], k[1]) for k in design.routes}
    for driver, sink in sorted(pairs):
        assert design.branch_key_between(driver, sink) == \
            _find_branch(design, driver, sink)
    # misses agree too (both sides return None)
    assert design.branch_key_between("no_such", "pair") is None
    assert _find_branch(design, "no_such", "pair") is None
    # the index survives — and is oblivious to — register mutation
    d = copy.deepcopy(design)
    post_pnr_pipeline(d, _routed(app, unroll)[1], PostPnRParams(max_iters=4))
    for driver, sink in sorted({(k[0], k[1]) for k in d.routes}):
        assert d.branch_key_between(driver, sink) == \
            _find_branch(d, driver, sink)


# ---------------------------------------------------------------------------
# stage-cache seam: sta_backend keys pipelined, not routed
# ---------------------------------------------------------------------------


def test_sta_backend_keys_pipelined_but_not_routed_stage():
    c = _compiler()
    app = ALL_APPS["gaussian"]
    cfg_s = PassConfig()
    cfg_n = PassConfig(sta_backend="numpy")
    args = (c.fabric, c.timing, c.energy)
    for stage, npre in (("mapped", 4), ("placed", 5), ("routed", 6)):
        prefix = DEFAULT_SCHEDULE[:npre]
        assert stage_key(app, cfg_s, *args, stage=stage, prefix=prefix) == \
            stage_key(app, cfg_n, *args, stage=stage, prefix=prefix)
    # ...but the pipelined artifact is keyed by the engine choice
    prefix = DEFAULT_SCHEDULE[:7]
    assert stage_key(app, cfg_s, *args, stage="pipelined", prefix=prefix) != \
        stage_key(app, cfg_n, *args, stage="pipelined", prefix=prefix)


def test_backend_field_reuses_routed_artifacts_end_to_end():
    """Two full compiles differing only in ``sta_backend`` produce
    identical designs and metrics (bit-identity is a config invariant, so
    the field exists purely as a speed knob)."""
    c = _compiler()
    r_s = c.compile(ALL_APPS["gaussian"], PassConfig(place_moves=20))
    r_n = c.compile(ALL_APPS["gaussian"],
                    PassConfig(place_moves=20, sta_backend="numpy"))
    assert _reg_state(r_s.design) == _reg_state(r_n.design)
    assert r_s.sta.critical_path_ns == r_n.sta.critical_path_ns
    assert r_s.power.scaled() == r_n.power.scaled()

