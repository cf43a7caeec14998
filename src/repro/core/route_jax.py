"""Batched wavefront routing — the ``"jax"`` PnR backend for ``route()``.

The Python router (:mod:`repro.core.route`) grows each driver's fanout tree
with one A* search per sink, one driver at a time.  This module keeps the
outer PathFinder negotiation loop on the host but replaces the per-driver
inner loop with a single jitted kernel: every *dirty* driver of a width
class is routed in the same call, ``vmap``-batched over the driver axis.
Per driver the kernel scans its sinks in the same nearest-first order as
the A* path and, per sink, runs a multi-source Bellman–Ford *wavefront*
relaxation over the dense ``(T, 4)`` in-edge cost array (T = every tile
including the north IO row): distances start at 0 on the current tree,
``lax.while_loop`` relaxes all tiles' four in-edges at once until no
distance improves, then the new branch is recovered by walking parent
pointers back from the sink.  One relaxation sweep is a handful of dense
``(T, 4)`` gathers/min-reductions — the wavefront over the whole fabric
costs what A* paid per heap pop.

Congestion pricing matches the Python path: an edge costs
``1 + present_fac * max(0, usage + 1 - cap) + history``, region-fenced
edges cost ``inf`` (the relaxation can never cross them), and overused
boundaries accrue history cost between iterations.  The one semantic
difference is negotiation *batching*: the Python router reroutes dirty
drivers sequentially, each seeing the usage left by the one before; the
batched kernel prices all dirty drivers of an iteration against the same
frozen usage snapshot (classic parallel PathFinder).  Routed trees are
cost-optimal against that snapshot, so wirelength matches A* on
uncongested fabrics and the history term resolves contention across
iterations exactly as before.

Contract with the A* path: same legality (connected trees, region fence,
capacity negotiation with the same non-convergence error), deterministic
(the kernel has no RNG at all — ties break by fixed direction order), but
bit-identical tree shapes are *not* promised where equal-cost paths tie.
``jax`` is imported lazily, keeping the default path import-free.
"""

from __future__ import annotations

import math
from functools import lru_cache
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..runtime.spans import span
from .interconnect import Fabric, Region, Tile, manhattan
from .netlist import Branch, Netlist

# direction order of the dense edge axes: matches interconnect.DIRS
_DIRS = ((-1, 0), (1, 0), (0, 1), (0, -1))          # N, S, E, W


def _tile_tables(fabric: Fabric, region: Optional[Region]):
    """Dense adjacency for the (rows+1) x cols tile grid (IO row included).

    Returns ``(T, out_nbr, in_src, in_dir)``: ``out_nbr[t, d]`` is the tile
    id reached from ``t`` in direction ``d`` (-1 when absent or when the
    edge would cross the region fence), and ``in_src/in_dir`` invert it —
    edge ``in_src[t, k] --in_dir[t, k]--> t`` exists for ``in_src >= 0``.
    """
    rows, cols = fabric.rows, fabric.cols
    T = (rows + 1) * cols

    def tid(t: Tile) -> int:
        return (t[0] + 1) * cols + t[1]

    out_nbr = np.full((T, 4), -1, dtype=np.int32)
    for t in fabric.tiles():
        allowed = set(fabric.neighbors(t))
        for d, (dr, dc) in enumerate(_DIRS):
            nt = (t[0] + dr, t[1] + dc)
            if nt not in allowed:
                continue
            if region is not None and not (region.contains(t)
                                           and region.contains(nt)):
                continue                      # region fence
            out_nbr[tid(t), d] = tid(nt)

    in_src = np.full((T, 4), -1, dtype=np.int32)
    in_dir = np.zeros((T, 4), dtype=np.int32)
    fill = np.zeros(T, dtype=np.int32)
    for u in range(T):
        for d in range(4):
            v = out_nbr[u, d]
            if v < 0:
                continue
            k = fill[v]
            in_src[v, k] = u
            in_dir[v, k] = d
            fill[v] += 1
    return T, out_nbr, in_src, in_dir


@lru_cache(maxsize=64)
def _jitted_router(T: int, D: int, S: int):
    """Build (and cache) the batched tree router for one padded shape.

    ``D`` drivers x ``S`` sinks over ``T`` tiles; pad drivers carry all-(-1)
    sink lists and route nothing.  Cached at module level so warm calls
    never re-trace (the jit-cache lesson from the placer applies here too).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    INF = jnp.float32(np.inf)

    def route_trees(in_src, in_dir, cost_out, drv_tile, sink_tiles):
        # in_cost[t, k]: cost of in-edge in_src[t, k] -> t (inf when absent)
        src = jnp.maximum(in_src, 0)
        in_cost = jnp.where(in_src >= 0, cost_out[src, in_dir], INF)
        iota = jnp.arange(T)

        def one_driver(drv, sinks):
            in_tree0 = jnp.zeros((T,), jnp.bool_).at[drv].set(True)

            def per_sink(in_tree, dst):
                dist0 = jnp.where(in_tree, jnp.float32(0), INF)
                parent0 = jnp.full((T,), -1, jnp.int32)

                def relax_cond(c):
                    return c[2]

                def relax(c):
                    dist, parent, _ = c
                    cand = jnp.where(in_src >= 0,
                                     dist[src] + in_cost, INF)     # (T, 4)
                    best = cand.min(axis=1)
                    bsrc = in_src[iota, cand.argmin(axis=1)]
                    improved = best < dist
                    return (jnp.where(improved, best, dist),
                            jnp.where(improved, bsrc, parent),
                            improved.any())

                dist, parent, _ = lax.while_loop(
                    relax_cond, relax, (dist0, parent0, jnp.bool_(True)))

                # walk parent pointers dst -> ... -> join; emit the join
                # tile, then -1 padding.  A pad sink (dst < 0) emits
                # nothing and leaves the tree untouched.
                valid = dst >= 0
                start = jnp.where(valid, dst, drv)

                def back(carry, _):
                    cur, done = carry
                    emit = jnp.where(done, -1, cur)
                    safe = jnp.maximum(cur, 0)
                    stop = done | in_tree[safe] | (parent[safe] < 0)
                    return (jnp.where(stop, cur, parent[safe]), stop), emit

                (_, _), path = lax.scan(back, (start, ~valid), None, length=T)
                grow = jnp.where(path >= 0, path, T)
                new_tree = in_tree.at[grow].set(True, mode="drop")
                return new_tree, (path, dist[jnp.maximum(dst, 0)])

            _, (paths, dcosts) = lax.scan(per_sink, in_tree0, sinks)
            return paths, dcosts                 # (S, T), (S,)

        return jax.vmap(one_driver)(drv_tile, sink_tiles)

    return jax.jit(route_trees)


def _edge_costs(usage: np.ndarray, history: np.ndarray, valid: np.ndarray,
                cap: int, present_fac: float) -> np.ndarray:
    """Dense congestion-priced out-edge costs (the Python ``cost()``,
    vectorized): ``1 + present_fac * max(0, usage + 1 - cap) + history``."""
    over = np.maximum(0, usage + 1 - cap).astype(np.float32)
    cost = 1.0 + present_fac * over + history
    return np.where(valid, cost, np.inf).astype(np.float32)


def _pad_pow2(k: int, lo: int = 1) -> int:
    return max(lo, 1 << (max(k, 1) - 1).bit_length())


def route_trees_jax(nl: Netlist, placement: Dict[str, Tile], fabric: Fabric,
                    by_driver: Dict[str, List[Branch]], p,
                    region: Optional[Region],
                    stats: Optional[dict] = None) -> Dict[
                        str, Dict[Tuple[str, str, int], List[Tile]]]:
    """Run the full negotiation loop with the batched kernel; returns the
    same ``driver -> branch-key -> tile path`` map the Python router builds
    (``route()`` finalizes both identically).

    Each negotiation iteration is a ``cascade.route.iter`` span and each
    kernel call, through the read-back of its results, a
    ``cascade.route.kernel`` span inside it.  ``stats`` (optional dict)
    gets ``iterations``, ``kernel_calls`` and the sorted padded ``(D, S)``
    shapes the kernel ran at."""
    T, out_nbr, in_src, in_dir = _tile_tables(fabric, region)
    cols = fabric.cols
    tid = lambda t: (t[0] + 1) * cols + t[1]
    untid = lambda i: (i // cols - 1, i % cols)
    width_class = lambda w: 16 if w >= 16 else 1

    valid = out_nbr >= 0
    cap = {wc: fabric.track_capacity(wc) for wc in (1, 16)}
    usage = {wc: np.zeros((T, 4), dtype=np.int32) for wc in (1, 16)}
    history = {wc: np.zeros((T, 4), dtype=np.float32) for wc in (1, 16)}

    # nearest-first sink order per driver — same growth order as the A* tree
    order: Dict[str, List[Branch]] = {
        drv: sorted(bs, key=lambda b: manhattan(placement[drv],
                                                placement[b.sink]))
        for drv, bs in by_driver.items()}
    drv_wc = {drv: width_class(bs[0].width) for drv, bs in by_driver.items()}

    tree_paths: Dict[str, Dict[Tuple[str, str, int], List[Tile]]] = {}
    tree_edges: Dict[str, set] = {}

    def edges_of(paths: Dict[Tuple[str, str, int], List[Tile]]) -> set:
        return {(tid(pth[i]), d)
                for pth in paths.values()
                for i in range(len(pth) - 1)
                for d in (_dir_of(pth[i], pth[i + 1]),)}

    def _dir_of(a: Tile, b: Tile) -> int:
        return _DIRS.index((b[0] - a[0], b[1] - a[1]))

    import jax.numpy as jnp

    drivers = list(by_driver)
    dirty = set(drivers)
    calls = 0
    shapes = set()
    for it in range(p.max_iters):
        with span("cascade.route.iter", iter=it, dirty=len(dirty)):
            # rip up every dirty driver first: the whole batch prices against
            # one frozen usage snapshot (parallel PathFinder)
            for drv in dirty:
                if drv in tree_edges:
                    wc = drv_wc[drv]
                    for t, d in tree_edges[drv]:
                        usage[wc][t, d] -= 1
            for wc in (1, 16):
                batch = [d for d in drivers if d in dirty and drv_wc[d] == wc]
                if not batch:
                    continue
                S = _pad_pow2(max(len(order[d]) for d in batch))
                D = _pad_pow2(len(batch))
                drv_tile = np.zeros(D, dtype=np.int32)
                sink_tiles = np.full((D, S), -1, dtype=np.int32)
                for i, drv in enumerate(batch):
                    drv_tile[i] = tid(placement[drv])
                    for s, b in enumerate(order[drv]):
                        sink_tiles[i, s] = tid(placement[b.sink])
                cost_out = _edge_costs(usage[wc], history[wc], valid,
                                       cap[wc], p.present_fac)
                kernel = _jitted_router(T, D, S)
                with span("cascade.route.kernel", T=T, D=D, S=S):
                    paths, dcosts = kernel(
                        jnp.asarray(in_src), jnp.asarray(in_dir),
                        jnp.asarray(cost_out), jnp.asarray(drv_tile),
                        jnp.asarray(sink_tiles))
                    paths = np.asarray(paths)
                    dcosts = np.asarray(dcosts)
                calls += 1
                shapes.add((D, S))
                for i, drv in enumerate(batch):
                    tree: Dict[Tile, List[Tile]] = {
                        placement[drv]: [placement[drv]]}
                    out: Dict[Tuple[str, str, int], List[Tile]] = {}
                    for s, b in enumerate(order[drv]):
                        if not math.isfinite(dcosts[i, s]):
                            raise RuntimeError(
                                f"unroutable: {drv} -> {b.sink}")
                        raw = paths[i, s]
                        part = [untid(int(x)) for x in raw[raw >= 0]][::-1]
                        join = part[0]
                        out[b.key] = tree[join][:-1] + part
                        for j in range(len(part) - 1):
                            t = part[j + 1]
                            if t not in tree:
                                tree[t] = tree[part[j]] + [t]
                    tree_paths[drv] = out
                    tree_edges[drv] = edges_of(out)
                    for t, d in tree_edges[drv]:
                        usage[wc][t, d] += 1

            over = {wc: usage[wc] > cap[wc] for wc in (1, 16)}
            if not any(o.any() for o in over.values()):
                break
            dirty = set()
            for wc in (1, 16):
                if not over[wc].any():
                    continue
                history[wc] += np.where(over[wc], p.history_fac, 0.0)
                hot = {(t, d) for t, d in zip(*np.nonzero(over[wc]))}
                for drv in drivers:
                    if drv_wc[drv] == wc and tree_edges[drv] & hot:
                        dirty.add(drv)
    else:
        n_over = int(sum(o.sum() for o in over.values()))
        if n_over:
            raise RuntimeError(
                f"{nl.name}: routing did not converge, {n_over} overused "
                f"boundaries after {p.max_iters} iterations")
    if stats is not None:
        stats.update(iterations=it + 1, kernel_calls=calls,
                     shapes=sorted(shapes))
    return tree_paths
