"""Multi-core PnR subsystem: vectorized SA kernel equivalence, the
process-backed ``compile_batch`` backend, the disk compile-cache tier, and
the env-var config plumbing."""

import json
import pickle

import numpy as np
import pytest

from repro.core import (ALL_APPS, CascadeCompiler, CompileCache, DiskCache,
                        PassConfig, cache_dir, worker_count)
from repro.core.cache import DISK_SCHEMA_VERSION
from repro.core.interconnect import Fabric
from repro.core.netlist import extract_netlist
from repro.core.pipelining import compute_pipelining
from repro.core.place import (PlaceParams, _net_cost, _net_cost_batch, _Nets,
                              place)


# ---------------------------------------------------------------------------
# vectorized SA kernel
# ---------------------------------------------------------------------------


def _random_netlist_arrays(rng, n_nodes=40, n_nets=25, max_deg=6):
    """Random positions + random padded net-terminal matrices."""
    pos = rng.integers(-1, 32, size=(n_nodes, 2)).astype(np.int64)
    term_mat = np.zeros((n_nets, max_deg), dtype=np.int64)
    term_count = np.zeros(n_nets, dtype=np.int64)
    nets = []
    for ni in range(n_nets):
        deg = int(rng.integers(2, max_deg + 1))
        term = rng.choice(n_nodes, size=deg, replace=False).astype(np.int64)
        nets.append(term)
        term_mat[ni, :deg] = term
        term_mat[ni, deg:] = term[0]
        term_count[ni] = deg
    return pos, nets, term_mat, term_count


def test_net_cost_batch_matches_scalar_bitwise_on_random_netlists():
    """Eq. 1 vectorized over padded matrices == the scalar reference,
    bit for bit, across random geometries and (gamma, alpha) corners."""
    rng = np.random.default_rng(7)
    for trial in range(5):
        pos, nets, term_mat, term_count = _random_netlist_arrays(rng)
        for gamma, alpha in ((0.3, 1.0), (0.3, 1.6), (0.0, 2.5), (1.7, 1.3)):
            batch = _net_cost_batch(pos, term_mat, term_count, gamma, alpha)
            scalar = [_net_cost(pos, t, gamma, alpha) for t in nets]
            for ni in range(len(nets)):
                assert batch[ni] == scalar[ni]   # bitwise, not approx


def test_padded_terminal_matrix_preserves_net_structure():
    nl = extract_netlist(ALL_APPS["unsharp"].build(1))
    nets = _Nets(nl)
    for ni, term in enumerate(nets.nets):
        row = nets.term_mat[ni]
        assert nets.term_count[ni] == len(term)
        assert set(row.tolist()) == set(term.tolist())   # padding repeats
        assert (row[len(term):] == term[0]).all()


def test_vectorized_place_matches_scalar_place_bitwise():
    """Same seed, both kernels: identical RNG stream, bit-identical costs,
    therefore identical accept/reject decisions and final placement."""
    g = ALL_APPS["harris"].build(1)
    compute_pipelining(g, 4)
    nl = extract_netlist(g)
    fab = Fabric()
    placements, stats = {}, {}
    for mode in ("numpy", "scalar"):
        st = {}
        placements[mode] = place(
            nl, fab, PlaceParams(alpha=1.6, seed=3, moves_per_node=40,
                                 backend=mode), stats=st)
        stats[mode] = st
    assert placements["numpy"] == placements["scalar"]
    assert stats["numpy"]["best_cost"] == stats["scalar"]["best_cost"]  # bitwise
    assert stats["numpy"]["moves_accepted"] == stats["scalar"]["moves_accepted"]
    assert stats["numpy"]["backend"] == "numpy"
    assert stats["scalar"]["backend"] == "scalar"


def test_place_debug_resync_passes_and_counts():
    """The per-temperature-step resync runs (and its assertions hold) on a
    real app under the debug flag."""
    nl = extract_netlist(ALL_APPS["vecadd"].build(1))
    st = {}
    place(nl, Fabric(), PlaceParams(seed=0, moves_per_node=20, debug=True),
          stats=st)
    assert st["resyncs"] > 0
    assert st["moves_evaluated"] >= st["moves_accepted"] > 0


def test_place_stats_surface_in_pass_stats():
    r = CascadeCompiler(cache=CompileCache()).compile(
        ALL_APPS["unsharp"], PassConfig.full(place_moves=20))
    ps = r.pass_stats["pnr"]["place"]
    assert ps["backend"] == "numpy"
    assert r.pass_stats["pass_times"]["place"] > 0
    assert ps["nodes"] > 0 and ps["nets"] > 0


# ---------------------------------------------------------------------------
# process-backed compile_batch
# ---------------------------------------------------------------------------


def _summaries(results):
    return [json.dumps(r.summary()) for r in results]


def test_process_backend_byte_identical_to_serial():
    jobs = [(ALL_APPS[a], PassConfig.full(place_moves=20))
            for a in ("unsharp", "vecadd")]
    serial = [CascadeCompiler(cache=CompileCache()).compile(
        app, cfg, use_cache=False) for app, cfg in jobs]
    c = CascadeCompiler(cache=CompileCache())
    batch = c.compile_batch(jobs, backend="process", max_workers=2)
    assert _summaries(batch) == _summaries(serial)
    assert c.last_batch["backend"] == "process"
    assert c.last_batch["compiled"] == 2 and c.last_batch["cache_hits"] == 0
    # and the parent merged the worker results into its cache
    again = c.compile_batch(jobs, backend="process")
    assert all(r.cache_hit for r in again)
    assert c.last_batch["compiled"] == 0 and c.last_batch["cache_hits"] == 2


def test_auto_backend_picks_process_only_for_multi_miss_batches():
    c = CascadeCompiler(cache=CompileCache())
    app = ALL_APPS["vecadd"]
    c.compile_batch([(app, PassConfig.full(place_moves=20))])
    assert c.last_batch["backend"] == "thread"     # single miss: no fork
    jobs = [(app, PassConfig.full(place_moves=20, seed=s)) for s in (1, 2)]
    c.compile_batch(jobs)
    assert c.last_batch["backend"] == "process"
    c.compile_batch(jobs)                          # warm: all hits
    assert c.last_batch["cache_hits"] == 2 and c.last_batch["compiled"] == 0


def test_jax_backend_jobs_compile_in_process(monkeypatch):
    """Only one process may hold the accelerator, so jax-kernel jobs never
    go to a process pool, even when more than one misses."""
    import repro.core.compiler as compiler_mod

    def no_pool(*a, **kw):
        raise AssertionError("a jax-backend job reached a process pool")

    monkeypatch.setattr(compiler_mod, "ProcessPoolExecutor", no_pool)
    c = CascadeCompiler(fabric=Fabric(rows=8, cols=8, mem_col_stride=4),
                        cache=CompileCache())
    app = ALL_APPS["vecadd"]
    jobs = [(app, PassConfig.full(place_moves=20, seed=s, pnr_backend="jax",
                                  pnr_replicas=2)) for s in (1, 2)]
    out = c.compile_batch(jobs, backend="auto")
    assert c.last_batch["backend"] == "thread"
    assert c.last_batch["compiled"] == 2
    assert all(r.sta.critical_path_ns > 0 for r in out)


def test_process_backend_unpicklable_job_falls_back_inline():
    app = ALL_APPS["vecadd"]
    # a closure builder cannot cross the process boundary
    from dataclasses import replace
    orig = ALL_APPS["elemmul"].builder
    weird = replace(ALL_APPS["elemmul"],
                    builder=lambda c, g, w: orig(c, g, w),
                    name="elemmul_closure")
    with pytest.raises(Exception):
        pickle.dumps(weird)
    c = CascadeCompiler(cache=CompileCache())
    out = c.compile_batch([(app, PassConfig.full(place_moves=20)),
                           (weird, PassConfig.full(place_moves=20))],
                          backend="process", max_workers=2)
    assert [r.summary()["app"] for r in out] == ["vecadd", "elemmul_closure"]
    assert c.last_batch["inline_fallback"] == 1


def test_lmmap_specs_are_picklable_for_process_jobs():
    from repro.configs import ARCHS
    from repro.core.lmmap import lower_block
    for cfg in list(ARCHS.values())[:3]:
        spec = lower_block(cfg)
        clone = pickle.loads(pickle.dumps(spec))
        assert clone.build(1).nodes.keys() == spec.build(1).nodes.keys()


def test_batch_results_are_independent_objects_even_on_dedup():
    """Duplicate jobs share one compile but must never share identity:
    mutating one batch result cannot corrupt another (or the cache)."""
    c = CascadeCompiler(cache=CompileCache())
    app = ALL_APPS["vecadd"]
    cfg = PassConfig.full(place_moves=20)
    out = c.compile_batch([(app, cfg), (app, cfg), (app, cfg)])
    assert c.cache.stats()["misses"] == 1          # deduped to one compile
    assert len({id(r) for r in out}) == 3
    assert len({id(r.design) for r in out}) == 3
    baseline = json.dumps(out[1].summary())
    out[0].design.placement.clear()                # vandalize result 0
    out[0].pass_stats["poison"] = True
    out[2].design.unroll_copies = 999
    assert json.dumps(out[1].summary()) == baseline
    assert out[1].design.placement and "poison" not in out[1].pass_stats
    fresh = c.compile_batch([(app, cfg)])[0]       # cache entry unharmed
    assert fresh.design.placement and "poison" not in fresh.pass_stats


def test_invalid_backend_rejected():
    with pytest.raises(ValueError):
        CascadeCompiler(cache=CompileCache()).compile_batch(
            [(ALL_APPS["vecadd"], None)], backend="mpi")
    with pytest.raises(ValueError):
        CascadeCompiler(batch_backend="mpi")


# ---------------------------------------------------------------------------
# disk cache tier
# ---------------------------------------------------------------------------


def test_disk_cache_round_trip_across_cache_instances(tmp_path):
    """A fresh memory cache (a new process, in effect) is served from disk."""
    disk = DiskCache(root=tmp_path)
    c1 = CascadeCompiler(cache=CompileCache(disk=disk))
    app, cfg = ALL_APPS["vecadd"], PassConfig.full(place_moves=20)
    r1 = c1.compile(app, cfg)
    assert not r1.cache_hit and disk.stats()["puts"] == 1
    c2 = CascadeCompiler(cache=CompileCache(disk=DiskCache(root=tmp_path)))
    r2 = c2.compile(app, cfg)
    assert r2.cache_hit
    assert json.dumps(r2.summary()) == json.dumps(r1.summary())
    assert c2.cache.disk.stats()["hits"] == 1


def test_disk_cache_invalidated_on_schema_version_bump(tmp_path):
    disk = DiskCache(root=tmp_path)
    disk.put("k" * 64, {"payload": 1})
    assert DiskCache(root=tmp_path).get("k" * 64) == {"payload": 1}
    bumped = DiskCache(root=tmp_path, schema=DISK_SCHEMA_VERSION + 1)
    assert bumped.get("k" * 64) is None            # new namespace: cold
    assert bumped.stats()["misses"] == 1


def test_disk_cache_namespace_isolates_code_changes(tmp_path):
    a = DiskCache(root=tmp_path, namespace="aaaa")
    b = DiskCache(root=tmp_path, namespace="bbbb")
    a.put("key1", "from-a")
    assert b.get("key1") is None
    assert a.get("key1") == "from-a"


def test_disk_cache_corrupt_entry_is_a_miss(tmp_path):
    disk = DiskCache(root=tmp_path)
    disk.put("deadbeef", [1, 2, 3])
    path = disk._path("deadbeef")
    path.write_bytes(b"not a pickle")
    assert disk.get("deadbeef") is None
    assert not path.exists()                       # corrupt entry removed


def test_disk_cache_bounded_size_evicts_oldest(tmp_path):
    import os
    import time as _time
    disk = DiskCache(root=tmp_path, max_bytes=4096)
    for i in range(8):
        disk.put(f"key{i}", os.urandom(400).hex())   # ~900B pickled
        _time.sleep(0.01)                            # distinct mtimes
    assert disk.size_bytes() <= 4096
    assert disk.stats()["evictions"] > 0
    assert disk.get("key7") is not None              # newest survives


def test_disk_cache_sweeps_stale_tmp_orphans(tmp_path):
    """A process killed mid-put strands a .tmp file; the next eviction
    sweep removes it once it is clearly not an in-flight write."""
    import os
    disk = DiskCache(root=tmp_path, max_bytes=1)    # every put trims
    orphan = disk.dir / "orphan.tmp"
    orphan.write_bytes(b"stranded")
    old = 120.0
    os.utime(orphan, (orphan.stat().st_atime - old,
                      orphan.stat().st_mtime - old))
    fresh = disk.dir / "inflight.tmp"
    fresh.write_bytes(b"writing")
    disk.put("key", "value")
    assert not orphan.exists()
    assert fresh.exists()                           # recent: left alone


def test_disk_cache_unpicklable_value_is_skipped(tmp_path):
    disk = DiskCache(root=tmp_path)
    disk.put("k", lambda: None)
    assert disk.stats()["put_errors"] == 1 and len(disk) == 0


# ---------------------------------------------------------------------------
# env-var config plumbing
# ---------------------------------------------------------------------------


def test_cache_dir_env_override(monkeypatch, tmp_path):
    monkeypatch.setenv("CASCADE_CACHE_DIR", str(tmp_path / "custom"))
    assert cache_dir() == tmp_path / "custom"
    disk = DiskCache()
    assert str(disk.dir).startswith(str(tmp_path / "custom"))
    monkeypatch.delenv("CASCADE_CACHE_DIR")
    assert cache_dir().name == "cascade-repro"


def test_worker_count_env_override(monkeypatch):
    monkeypatch.setenv("CASCADE_WORKERS", "3")
    assert worker_count() == 3
    monkeypatch.setenv("CASCADE_WORKERS", "not-a-number")
    assert worker_count(jobs=2) <= 2               # falls back, job-clamped
    monkeypatch.delenv("CASCADE_WORKERS")
    assert 1 <= worker_count() <= 8


def test_worker_count_env_clamped_to_jobs(monkeypatch):
    """Regression: the env path must honour the docstring's "never more
    than jobs" clamp — CASCADE_WORKERS=8 with a 2-job batch is 2 workers,
    not 8 idle ones."""
    monkeypatch.setenv("CASCADE_WORKERS", "8")
    assert worker_count(jobs=2) == 2
    assert worker_count(jobs=1) == 1
    assert worker_count(jobs=16) == 8              # env still caps upward
    assert worker_count() == 8                     # no jobs: env verbatim
    monkeypatch.setenv("CASCADE_WORKERS", "0")
    assert worker_count(jobs=4) == 1               # floor stays at 1


def test_compile_batch_honours_cascade_workers(monkeypatch):
    monkeypatch.setenv("CASCADE_WORKERS", "2")
    c = CascadeCompiler(cache=CompileCache())
    cfg = PassConfig.full(place_moves=20)
    c.compile_batch([(ALL_APPS["vecadd"], cfg), (ALL_APPS["ttv"], cfg)])
    assert c.last_batch["workers"] == 2
    # env value is still clamped to the job count (worker_count contract)
    c2 = CascadeCompiler(cache=CompileCache())
    c2.compile_batch([(ALL_APPS["vecadd"], cfg)])
    assert c2.last_batch["workers"] == 1
