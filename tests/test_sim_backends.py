"""Vectorized simulator backends: 3-way bit-identity with the interpreter
oracle, lowering guards, deadlock diagnostics, and the reference-stream
memo behind ``equivalent``/``sparse_equivalent``."""

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from test_cascade_core import _inputs_for, random_dfg, random_pred_dfg

from repro.core import (DENSE_APPS, SPARSE_APPS, SIM_BACKENDS,
                        SimLoweringError, clear_ref_memo, equivalent,
                        lower_dense, simulate, simulate_sparse,
                        sparse_equivalent)
from repro.core.dfg import DFG, INPUT, MEM, OUTPUT, PE
from repro.core.pipelining import compute_pipelining
from repro.core.sim import ref_memo_stats

VEC_BACKENDS = ("numpy", "jax")


def _dense_inputs(g, cycles, seed=0):
    rng = np.random.default_rng(seed)
    return {n: rng.integers(0, 0x10000, size=cycles).tolist()
            for n, nd in g.nodes.items() if nd.kind == INPUT}


def _sparse_inputs(g, tokens, seed=0):
    rng = np.random.default_rng(seed)
    return {n: rng.integers(0, 0x10000, size=tokens).tolist()
            for n, nd in g.nodes.items() if nd.kind == INPUT}


# ---------------------------------------------------------------------------
# bit identity on the benchmark suites
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", VEC_BACKENDS)
@pytest.mark.parametrize("app", sorted(DENSE_APPS))
def test_dense_backend_bit_identical_on_bench_apps(app, backend):
    g = DENSE_APPS[app].build(1)
    cycles = 96
    ins = _dense_inputs(g, cycles)
    ref = simulate(g, ins, cycles)
    assert simulate(g, ins, cycles, backend=backend) == ref


@pytest.mark.parametrize("backend", VEC_BACKENDS)
@pytest.mark.parametrize("app", sorted(SPARSE_APPS))
def test_sparse_backend_bit_identical_on_bench_apps(app, backend):
    g = SPARSE_APPS[app].build(1)
    ins = _sparse_inputs(g, 48)
    ref = simulate_sparse(g, ins, 4096)
    assert simulate_sparse(g, ins, 4096, backend=backend) == ref


@pytest.mark.parametrize("backend", VEC_BACKENDS)
def test_dense_backend_deterministic_across_calls(backend):
    g = DENSE_APPS["gaussian"].build(1)
    ins = _dense_inputs(g, 64, seed=7)
    a = simulate(g, ins, 64, backend=backend)
    b = simulate(g, ins, 64, backend=backend)
    assert a == b


@settings(max_examples=25, deadline=None)
@given(random_dfg(), st.integers(0, 3))
def test_dense_backends_match_interpreter_on_random_dags(g, seed):
    """Property: on random matched DAGs every vectorized backend's output
    streams are byte-equal to the interpreter's."""
    ins = _inputs_for(g, seed, n=32)
    ref = simulate(g, ins, 32)
    for backend in VEC_BACKENDS:
        assert simulate(g, ins, 32, backend=backend) == ref, backend


@settings(max_examples=25, deadline=None)
@given(random_pred_dfg(), st.integers(0, 3))
def test_dense_backends_match_interpreter_on_predicated_dags(g, seed):
    """Property: comparators, mux, steer/sel/phi, and predicated
    accumulators lower bit-identically to the interpreter oracle."""
    ins = _inputs_for(g, seed, n=32)
    ref = simulate(g, ins, 32)
    for backend in VEC_BACKENDS:
        assert simulate(g, ins, 32, backend=backend) == ref, backend


def test_unknown_backend_rejected():
    g = DENSE_APPS["gaussian"].build(1)
    with pytest.raises(ValueError, match="unknown sim backend"):
        simulate(g, _dense_inputs(g, 4), 4, backend="cuda")
    with pytest.raises(ValueError, match="unknown sim backend"):
        simulate_sparse(g, {}, 4, backend="cuda")


# ---------------------------------------------------------------------------
# lowering guards: the vectorized contract is the 16-bit domain
# ---------------------------------------------------------------------------


def test_out_of_domain_inputs_raise_lowering_error():
    g = DENSE_APPS["gaussian"].build(1)
    ins = _dense_inputs(g, 8)
    bad = dict(ins)
    bad[next(iter(bad))] = [0x10000] * 8     # one past the 16-bit domain
    for backend in VEC_BACKENDS:
        with pytest.raises(SimLoweringError):
            simulate(g, bad, 8, backend=backend)
    neg = dict(ins)
    neg[next(iter(neg))] = [-1] * 8
    with pytest.raises(SimLoweringError):
        simulate(g, neg, 8, backend="numpy")


def test_sim_lowering_error_is_value_error():
    assert issubclass(SimLoweringError, ValueError)


def test_lower_dense_signature_is_hashable_and_stable():
    g = DENSE_APPS["harris"].build(1)
    p1, p2 = lower_dense(g), lower_dense(g)
    assert p1.signature() == p2.signature()
    hash(p1.signature())                      # jit factories key on this


# ---------------------------------------------------------------------------
# ROM with no address edge (regression: IndexError in the interpreter)
# ---------------------------------------------------------------------------


def _rom_no_addr_graph():
    g = DFG("romfix")
    i = g.add(INPUT, name="i")
    rom = g.add(MEM, name="lut", op="rom", latency=1,
                meta={"table": [42, 7, 9]})
    s = g.add(PE, name="s", op="add")
    g.connect(i, s, port=0)
    g.connect(rom, s, port=1)                 # rom has *no* address input
    o = g.add(OUTPUT, name="o")
    g.connect(s, o)
    return g.validate()


def test_rom_without_address_reads_entry_zero_everywhere():
    g = _rom_no_addr_graph()
    ins = {"i": list(range(8))}
    ref = simulate(g, ins, 8)                 # used to IndexError
    assert ref["o"][1:] == [t + 42 for t in range(1, 8)]
    for backend in VEC_BACKENDS:
        assert simulate(g, ins, 8, backend=backend) == ref


# ---------------------------------------------------------------------------
# sparse deadlock diagnostics name the stalled nodes and ports
# ---------------------------------------------------------------------------


def _starved_graph():
    g = DFG("starve")
    a = g.add(INPUT, name="a")
    b = g.add(INPUT, name="b")
    pe = g.add(PE, name="mix", op="add")
    g.connect(a, pe, port=0)
    g.connect(b, pe, port=1)
    o = g.add(OUTPUT, name="o")
    g.connect(pe, o)
    return g.validate()


@pytest.mark.parametrize("backend", ("interpreter",) + VEC_BACKENDS)
def test_sparse_deadlock_message_names_starved_port(backend):
    g = _starved_graph()
    ins = {"a": [1, 2, 3], "b": [5]}          # b dries up after one token
    with pytest.raises(RuntimeError) as ei:
        simulate_sparse(g, ins, 64, backend=backend)
    msg = str(ei.value)
    # token 1 is consumed, token 2 sits in mix's skid buffer, token 3
    # stays pending at the feed
    assert "1 input token(s) pending" in msg
    assert "mix" in msg and "p1<-b" in msg    # the starved port, by name


def test_sparse_deadlock_message_identical_across_backends():
    g = _starved_graph()
    ins = {"a": [1, 2, 3], "b": [5]}
    msgs = set()
    for backend in ("interpreter",) + VEC_BACKENDS:
        with pytest.raises(RuntimeError) as ei:
            simulate_sparse(g, ins, 64, backend=backend)
        msgs.add(str(ei.value))
    assert len(msgs) == 1                     # unique quiescent marking


# ---------------------------------------------------------------------------
# equivalent()/sparse_equivalent parity + reference-stream memo
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", SIM_BACKENDS)
def test_equivalent_parity_across_backends(backend):
    ref = DENSE_APPS["gaussian"].build(1)
    xform = ref.copy()
    compute_pipelining(xform, rf_threshold=3)
    ins = _inputs_for(ref, seed=3)
    assert equivalent(ref, xform, ins, n=32, backend=backend)


@pytest.mark.parametrize("backend", SIM_BACKENDS)
def test_sparse_equivalent_parity_across_backends(backend):
    ref = SPARSE_APPS["vecadd"].build(1)
    ins = _sparse_inputs(ref, 24)
    assert sparse_equivalent(ref, ref.copy(), ins, backend=backend)


def test_equivalent_memoizes_reference_streams():
    clear_ref_memo()
    ref = DENSE_APPS["gaussian"].build(1)
    xform = ref.copy()
    compute_pipelining(xform, rf_threshold=3)
    ins = _inputs_for(ref, seed=5)
    assert equivalent(ref, xform, ins, n=32)
    misses0 = ref_memo_stats["misses"]
    assert misses0 >= 1
    # same reference + inputs: served from the memo, no new miss
    assert equivalent(ref, xform, ins, n=32)
    assert equivalent(ref, xform, ins, n=16)  # prefix of the cached stream
    assert ref_memo_stats["misses"] == misses0
    assert ref_memo_stats["hits"] >= 2
    # different inputs -> different key -> fresh miss
    assert equivalent(ref, xform, _inputs_for(ref, seed=6), n=32)
    assert ref_memo_stats["misses"] == misses0 + 1
    clear_ref_memo()
    assert ref_memo_stats == {"hits": 0, "misses": 0}

