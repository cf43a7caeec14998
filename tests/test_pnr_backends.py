"""Backend-equivalence properties for the PnR kernel seam.

The ``jax`` backend (jitted parallel-tempering placer + batched wavefront
router) is not bit-identical to the ``scalar``/``numpy`` oracle pair, but
it must be *legal* by the same structural rules, deterministic per seed,
cost-competitive, and keyed into the stage cache at the placed/routed
boundary.  These tests pin each of those contracts, plus the config-side
helpers (``CASCADE_PNR_BACKEND``, the host-device-count resolver).

Every jax test reuses one tiny problem shape so the suite pays for a
handful of XLA compiles, not one per test.
"""

import warnings

import numpy as np
import pytest

from repro.core import (ALL_APPS, CascadeCompiler, PassConfig, Region,
                        host_device_count, pnr_backend)
from repro.core.cache import stage_key
from repro.core.config import PNR_BACKENDS
from repro.core.interconnect import Fabric
from repro.core.netlist import extract_netlist
from repro.core.passes import DEFAULT_SCHEDULE
from repro.core.place import IO_CAPACITY, PlaceParams, place
from repro.core.route import RouteParams, route

jax = pytest.importorskip("jax")

FABRIC = Fabric()


def _netlist(app="vecadd", mult=1):
    return extract_netlist(ALL_APPS[app].build(mult))


def assert_legal_placement(nl, placement, fabric, region=None):
    """The structural legality every backend must satisfy: class-correct
    tiles, no PE/MEM site sharing, IO sites at most ``IO_CAPACITY``-deep,
    and (when fenced) full region containment."""
    from repro.core.place import TILE_CLASS
    io_load = {}
    seen = set()
    for name, tile in placement.items():
        kind = TILE_CLASS[nl.nodes[name].kind]
        assert fabric.tile_kind(tile) == kind, (name, tile)
        if region is not None:
            assert region.contains(tile), (name, tile)
        if kind == "io":
            io_load[tile] = io_load.get(tile, 0) + 1
        else:
            assert tile not in seen, f"site conflict at {tile}"
            seen.add(tile)
    assert all(v <= IO_CAPACITY for v in io_load.values())


def assert_legal_routes(design, placement, fabric, region=None):
    """Connectivity, adjacency, capacity, and (when fenced) containment."""
    per_driver = {}
    for (drv, sink, _), rb in design.routes.items():
        tiles = ([rb.hops[0].src] + [h.dst for h in rb.hops]
                 if rb.hops else [placement[drv]])
        assert tiles[0] == placement[drv]
        assert tiles[-1] == placement[sink]
        for h in rb.hops:
            assert h.dst in fabric.neighbors(h.src), h
            if region is not None:
                assert region.contains(h.src) and region.contains(h.dst)
        wc = 16 if rb.branch.width >= 16 else 1
        per_driver.setdefault(drv, set()).update(
            (h.src, h.dst, wc) for h in rb.hops)
    usage = {}
    for edges in per_driver.values():
        for e in edges:
            usage[e] = usage.get(e, 0) + 1
    over = {k: u for k, u in usage.items()
            if u > fabric.track_capacity(k[2])}
    assert not over, over


def _wirelength(design):
    return sum(len(rb.hops) for rb in design.routes.values())


# ---------------------------------------------------------------------------
# placement: legality, determinism, cost tolerance across backends
# ---------------------------------------------------------------------------


def test_all_place_backends_legal_and_cost_comparable():
    nl = _netlist("vecadd")
    costs = {}
    for backend in PNR_BACKENDS:
        s = {}
        pl = place(nl, FABRIC, PlaceParams(seed=2, moves_per_node=60,
                                           backend=backend,
                                           proposal_block=8), stats=s)
        assert s["backend"] == backend
        assert_legal_placement(nl, pl, FABRIC)
        costs[backend] = s["best_cost"]
    # scalar and numpy are the bit-identical PR 2 pair; jax anneals the
    # same Eq. 1 objective with a replica ensemble and must land within
    # tolerance of (in practice, below) the single-chain result
    assert costs["scalar"] == costs["numpy"]
    assert costs["jax"] <= costs["numpy"] * 1.10


def test_jax_placement_deterministic_per_seed():
    nl = _netlist("vecadd")
    pp = PlaceParams(seed=5, moves_per_node=60, backend="jax",
                     proposal_block=8)
    a = place(nl, FABRIC, pp)
    b = place(nl, FABRIC, pp)
    assert a == b
    c = place(nl, FABRIC, PlaceParams(seed=6, moves_per_node=60,
                                      backend="jax", proposal_block=8))
    assert c != a   # the seed actually steers the ensemble


def test_jax_placement_region_fenced():
    """Reuses test_multi's no-site-leaves-region property for the jax
    kernel: the site pools are region-filtered before dispatch, so every
    replica proposes only in-region sites."""
    nl = _netlist("vecadd")
    region = Region(0, 8, 32, 8)
    pl = place(nl, FABRIC, PlaceParams(seed=1, moves_per_node=60,
                                       backend="jax", proposal_block=8),
               region=region)
    assert_legal_placement(nl, pl, FABRIC, region=region)


def test_jax_replica_ensemble_stats_surface():
    nl = _netlist("vecadd")
    s = {}
    place(nl, FABRIC, PlaceParams(seed=0, moves_per_node=60, backend="jax",
                                  replicas=2, proposal_block=8), stats=s)
    assert s["replicas"] == 2
    assert s["devices"] >= 1
    assert len(s["replica_costs"]) == 2
    assert s["best_replica"] in (0, 1)
    assert s["best_cost"] == pytest.approx(min(s["replica_costs"]), rel=1e-5)


# ---------------------------------------------------------------------------
# routing: legality, determinism, wirelength parity, region fence
# ---------------------------------------------------------------------------


def test_jax_routes_legal_and_wirelength_matches_astar():
    nl = _netlist("vecadd")
    pl = place(nl, FABRIC, PlaceParams(seed=2, moves_per_node=60))
    rd_np = route(nl, pl, FABRIC)
    rd_j = route(nl, pl, FABRIC, RouteParams(backend="jax"))
    assert_legal_routes(rd_j, pl, FABRIC)
    # both searches are cost-optimal per sink against the same congestion
    # pricing, so total wirelength must not regress
    assert _wirelength(rd_j) <= _wirelength(rd_np)


def test_jax_route_deterministic():
    nl = _netlist("vecadd")
    pl = place(nl, FABRIC, PlaceParams(seed=2, moves_per_node=60))
    a = route(nl, pl, FABRIC, RouteParams(backend="jax"))
    b = route(nl, pl, FABRIC, RouteParams(backend="jax"))
    assert all([h for h in a.routes[k].hops] == [h for h in b.routes[k].hops]
               for k in a.routes)


def test_jax_route_region_fenced():
    nl = _netlist("vecadd")
    region = Region(0, 8, 32, 8)
    pl = place(nl, FABRIC, PlaceParams(seed=1, moves_per_node=60),
               region=region)
    rd = route(nl, pl, FABRIC.subregion(region),
               RouteParams(backend="jax"), region=region)
    assert_legal_routes(rd, pl, FABRIC, region=region)


def test_check_legal_rejects_broken_routes():
    """The legality backstop every route() runs: a route that stops short
    of its sink, or one that leaves its region, is refused."""
    import copy
    from repro.core.route import check_legal
    nl = _netlist("vecadd")
    pl = place(nl, FABRIC, PlaceParams(seed=2, moves_per_node=60))
    design = route(nl, pl, FABRIC)
    check_legal(design)
    key = next(k for k, rb in design.routes.items() if len(rb.hops) > 1)
    short = copy.deepcopy(design)
    short.routes[key].hops.pop()
    with pytest.raises(RuntimeError, match="does not join its endpoints"):
        check_legal(short)
    with pytest.raises(RuntimeError, match="left region"):
        check_legal(design, region=Region(0, 0, 1, 1))


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown place backend"):
        place(_netlist(), FABRIC, PlaceParams(backend="torch"))
    with pytest.raises(ValueError, match="unknown route backend"):
        route(_netlist(), {}, FABRIC, RouteParams(backend="torch"))


# ---------------------------------------------------------------------------
# stage-cache seam: pnr_backend keys placed/routed, not mapped
# ---------------------------------------------------------------------------


def test_pnr_backend_keys_placed_but_not_mapped_stage():
    c = CascadeCompiler()
    app = ALL_APPS["gaussian"]
    cfg_np = PassConfig(pnr_backend="numpy", place_moves=20)
    cfg_j = PassConfig(pnr_backend="jax", place_moves=20)
    args = (c.fabric, c.timing, c.energy)
    for stage, npre in (("mapped", 4), ("placed", 5), ("routed", 6)):
        prefix = DEFAULT_SCHEDULE[:npre]
        kn = stage_key(app, cfg_np, *args, stage=stage, prefix=prefix)
        kj = stage_key(app, cfg_j, *args, stage=stage, prefix=prefix)
        if stage == "mapped":
            assert kn == kj     # physical prefix shared across backends
        else:
            assert kn != kj     # kernels differ from placement on
    # replica count keys the placed stage too (a different ensemble is a
    # different anneal)
    cfg_r = PassConfig(pnr_backend="jax", pnr_replicas=2, place_moves=20)
    assert (stage_key(app, cfg_j, *args, stage="placed",
                      prefix=DEFAULT_SCHEDULE[:5])
            != stage_key(app, cfg_r, *args, stage="placed",
                         prefix=DEFAULT_SCHEDULE[:5]))


def test_compile_end_to_end_with_jax_backend():
    c = CascadeCompiler()
    r = c.compile(ALL_APPS["vecadd"],
                  PassConfig(pnr_backend="jax", pnr_replicas=2,
                             place_moves=20))
    st = r.pass_stats["pnr"]["place"]
    assert st["backend"] == "jax" and st["replicas"] == 2
    assert r.design.total_wirelength() > 0


# ---------------------------------------------------------------------------
# config helpers: CASCADE_PNR_BACKEND / CASCADE_HOST_DEVICES
# ---------------------------------------------------------------------------


def test_pnr_backend_env(monkeypatch):
    monkeypatch.delenv("CASCADE_PNR_BACKEND", raising=False)
    assert pnr_backend() == "numpy"
    monkeypatch.setenv("CASCADE_PNR_BACKEND", "jax")
    assert pnr_backend() == "jax"
    monkeypatch.setenv("CASCADE_PNR_BACKEND", "cuda")
    with pytest.warns(UserWarning, match="CASCADE_PNR_BACKEND"):
        assert pnr_backend() == "numpy"


def test_host_device_count_env(monkeypatch):
    monkeypatch.delenv("CASCADE_HOST_DEVICES", raising=False)
    assert host_device_count() == 1
    monkeypatch.setenv("CASCADE_HOST_DEVICES", "2")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # may oversubscribe a 1-cpu box
        assert host_device_count() == 2
    monkeypatch.setenv("CASCADE_HOST_DEVICES", "two")
    with pytest.warns(UserWarning, match="CASCADE_HOST_DEVICES"):
        assert host_device_count() == 1
    # explicit n beats the env var; the cap clamps
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert host_device_count(99) == 8
