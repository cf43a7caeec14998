"""Launch-layer units: roofline math, collective parsing, probe configs,
cell bookkeeping, pipeline partitioning properties."""

import os

import jax
import numpy as np
import pytest

from _hypothesis_compat import example, given, settings, st

# lock the backend to the default single device BEFORE repro.launch.dryrun
# (imported lazily below) sets XLA_FLAGS for 512 placeholder devices — the
# flag only affects fresh processes, and this guard makes that deterministic
jax.devices()

from repro.configs import ARCHS, SHAPES, get_config, model_flops
from repro.distributed.pipeline import (layer_costs, naive_partition,
                                        partition, plan_for)

# NOTE: repro.launch.dryrun sets XLA_FLAGS for 512 host devices at import,
# which must not leak into this test process's jax runtime — so only the
# pure helpers are imported lazily inside tests that need them, guarded to
# not initialize jax backends.


def test_wire_factors():
    import importlib.util, sys, os
    # parse/roofline helpers are pure python; import via spec without
    # triggering jax device init is unnecessary since jax is already
    # initialized (1 device) — the XLA_FLAGS set at import time only
    # matters for fresh processes.
    from repro.launch import dryrun as D
    assert D._wire_factor("all-reduce", 16) == pytest.approx(2 * 15 / 16)
    assert D._wire_factor("all-gather", 16) == pytest.approx(15 / 16)
    assert D._wire_factor("reduce-scatter", 16) == 15
    assert D._wire_factor("collective-permute", 2) == 1.0
    assert D._wire_factor("all-reduce", 1) == 0.0


def test_parse_collectives_counts_shapes_and_groups():
    from repro.launch import dryrun as D
    hlo = """
  %ag = bf16[16,512]{1,0} all-gather(bf16[16,32]{1,0} %x), replica_groups={{0,1,2,3}}, dimensions={1}
  %ar = (f32[128]{0}, f32[64]{0}) all-reduce(%a, %b), replica_groups=[2,8]<=[16], to_apply=%sum
  %other = f32[4]{0} add(f32[4]{0} %p, f32[4]{0} %q)
"""
    out = D.parse_collectives(hlo)
    ag = 16 * 512 * 2 * (3 / 4)
    ar = (128 * 4 + 64 * 4) * 2 * (7 / 8)
    assert out["per_op_bytes"]["all-gather"] == pytest.approx(ag)
    assert out["per_op_bytes"]["all-reduce"] == pytest.approx(ar)
    assert out["per_op_counts"]["all-gather"] == 1
    assert out["bytes_per_device"] == pytest.approx(ag + ar)


def test_roofline_terms_dominance():
    from repro.launch import dryrun as D
    r = D.roofline_terms(197e12, 819e9 * 2, 50e9 * 0.5)
    assert r["compute_s"] == pytest.approx(1.0)
    assert r["memory_s"] == pytest.approx(2.0)
    assert r["collective_s"] == pytest.approx(0.5)
    assert r["bound"] == "memory"
    assert r["step_time_lower_bound_s"] == 2.0


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_probe_configs_cover_structure(arch):
    from repro.launch import dryrun as D
    cfg = get_config(arch)
    u = D.probe_unit(cfg)
    assert cfg.num_layers % u == 0
    p1, p2 = D.make_probe_cfg(cfg, 1), D.make_probe_cfg(cfg, 2)
    assert p1.num_layers == u and p2.num_layers == 2 * u
    assert not p1.scan_layers and p1.attn_impl == "einsum"
    if cfg.family == "audio":
        assert p2.encoder_layers == 2 * p1.encoder_layers


def test_model_flops_kinds():
    cfg = get_config("llama3-8b")
    tr = model_flops(cfg, SHAPES["train_4k"])
    pf = model_flops(cfg, SHAPES["prefill_32k"])
    dc = model_flops(cfg, SHAPES["decode_32k"])
    n = cfg.param_count()
    assert tr == pytest.approx(6 * n * 4096 * 256)
    assert pf == pytest.approx(2 * n * 32768 * 32)
    assert dc == pytest.approx(2 * n * 128)
    # MoE counts active params only
    moe = get_config("llama4-maverick-400b-a17b")
    assert model_flops(moe, SHAPES["train_4k"]) < \
        6 * moe.param_count() * 4096 * 256 / 10


# ---------------------------------------------------------------------------
# pipeline partitioning properties


@settings(max_examples=40, deadline=None)
@given(st.lists(st.floats(0.1, 10.0), min_size=8, max_size=64),
       st.integers(2, 6), st.floats(0.0, 0.5))
# a plateau: the better split needs both boundaries to move
@example([7.0, 3.0, 1.0, 6.0, 4.0, 1.0, 1.0, 8.0], 3, 0.0)
def test_partition_never_much_worse_than_naive(costs, stages, bcost):
    cas = partition(costs, stages, bcost)
    nai = naive_partition(costs, stages, bcost)
    # the cascade loop must never lose by more than a whisker, and its
    # boundaries must be sane
    assert cas.beat_s <= nai.beat_s * 1.25
    assert cas.boundaries[0] == 0 and cas.boundaries[-1] == len(costs)
    assert all(b2 > b1 for b1, b2 in zip(cas.boundaries, cas.boundaries[1:]))
    # the beat can never be below the heaviest single layer
    assert cas.beat_s >= max(costs) - 1e-9


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 5))
def test_partition_competitive_on_spiky_stacks(stages):
    """Heterogeneous (spiky) stacks: the greedy break+rebalance loop must
    stay within 10% of the equal-count split everywhere (it strictly wins
    on real heterogeneous stacks — see test_system's zamba2 check)."""
    costs = ([1.0, 1.0, 1.0, 8.0] * 8)
    cas = partition(costs, stages, 0.0)
    nai = naive_partition(costs, stages, 0.0)
    assert cas.beat_s <= nai.beat_s * 1.10 + 1e-9


def test_layer_costs_reflect_heterogeneity():
    costs = layer_costs(ARCHS["zamba2-2.7b"], SHAPES["train_4k"],
                        chips_per_stage=64)
    assert len(costs) == 54
    # shared-attention layers (every 6th) cost more than plain mamba layers
    shared = [costs[i] for i in range(5, 54, 6)]
    plain = [costs[i] for i in range(54) if (i + 1) % 6]
    assert min(shared) > max(plain)


def test_peaks_are_keyed_by_device_kind():
    from repro.distributed.peaks import TPU_V5E, peaks_for
    assert peaks_for(TPU_V5E).flops == 197e12
    with pytest.raises(KeyError, match="cpu"):
        peaks_for("cpu")


def test_compile_cache_honours_env_and_else_uses_checkout_dir(monkeypatch):
    import jax
    from repro.launch import jax_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert jax_cache.use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = jax_cache.use_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
