"""Jitted parallel-tempering SA placement — the ``"jax"`` PnR backend.

The NumPy annealer (:mod:`repro.core.place`) evaluates one Metropolis move
per Python-loop iteration; this module runs ``PlaceParams.replicas``
chains at once as a single jitted program and, within each chain,
evaluates a *block* of ``PlaceParams.proposal_block`` move proposals per
step against the frozen state — the per-move Eq. 1 delta is the same
padded net-terminal gather as the NumPy kernel, batched over
``(replicas, block)`` in one XLA gather instead of one tiny NumPy kernel
per move.  Accepted proposals in a block are applied together under an
order-deterministic conflict rule (a proposal is dropped if an
earlier-in-block accepted proposal touches any of its nodes or sites —
so the site↔node bijection can never be corrupted; two kept moves *may*
share a net, which is safe because per-net costs carry no incremental
state).  Per-net costs are re-derived from the site assignment at every
step with one dense gather plus a host-precomputed ``(hpwl, area)``
power-lookup table (``pow`` transcendentals dominated an earlier
formulation), and the kept moves land through two ``mode="drop"``
scatters whose index count is the block size, not the slot count.

The temperature schedule is a ``lax.scan``; after every temperature step
adjacent replicas of the geometric temperature ladder attempt a
Metropolis state exchange, so extra replicas (and extra devices: the
replica axis is sharded across the JAX mesh when more than one device is
live) buy placement *quality* as well as speed.  The best assignment
seen by any replica at any point in the anneal is the result.

Contract with the other backends (the PR 2 oracle playbook):

* legality is structural — proposals draw from the same region-filtered
  site pools as the NumPy/scalar kernels, and site occupancy is an
  explicit bijection updated only by conflict-free moves, so no
  accepted block can alias a site or leave the region;
* bit-identity across backends is *not* promised (float32 vs float64, a
  different RNG, block-parallel acceptance), but a fixed ``seed`` gives
  identical results run to run, and the best-replica cost is expected to
  be at or below the single-chain NumPy cost (the benchmark asserts it);
* ``jax`` is imported lazily so the NumPy/scalar paths never pay for it
  (and ``compile_batch``'s fork-based process backend stays available).

Use :func:`repro.core.config.force_host_device_count` before first jax
use to widen a CPU-only mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
"""

from __future__ import annotations

import math
from functools import lru_cache
from typing import Dict, List, Tuple

import numpy as np

from ..runtime.spans import span

# class order defines the flattened site-slot space: [pe | mem | io]
_CLASS_ORDER = ("pe", "mem", "io")


@lru_cache(maxsize=128)
def _jitted_anneal(n: int, n_nets: int, n_slots: int, replicas: int,
                   K: int, n_temps: int, blocks_per_temp: int):
    """Build (and cache) the jitted annealer for one static problem shape.

    Everything shape-like is baked into the compiled program; the netlist
    tables, initial state, and Eq. 1 hyperparameters are traced arguments,
    so repeated ``place()`` calls — and different seeds, alphas, gammas, or
    regions of the *same* shape — reuse one XLA executable.  (An earlier
    formulation jitted a fresh closure per call and every "warm" run paid
    ~2 s of recompilation, drowning the anneal itself.)
    """
    import jax
    import jax.numpy as jnp
    from jax import lax, random

    f32 = jnp.float32
    i32 = jnp.int32

    def anneal(tables, state, temps, key, t_factor):
        (site_rc, node_off, node_pool, node_nets,
         term_mat, term_count, pow_tab) = tables
        # pow_tab[hpwl, area] = (hpwl + gamma * area) ** alpha precomputed
        # on the host: hpwl and pass-through area are small fabric-bounded
        # integers, so Eq. 1 becomes one table gather and the kernel has
        # no transcendentals at all

        def all_net_costs(pos):
            """Eq. 1 over every net from scratch — one dense gather, no
            incremental state to drift."""
            pts = pos[term_mat]                          # (n_nets, D, 2)
            w = pts[..., 1].max(axis=1) - pts[..., 1].min(axis=1)
            h = pts[..., 0].max(axis=1) - pts[..., 0].min(axis=1)
            area = jnp.maximum(0, (w + 1) * (h + 1) - term_count)
            return pow_tab[w + h, area]

        def block_step(st, key):
            site, occ, best_cost, best_site, ev, acc, temp = st
            pos = site_rc[site]                          # (n, 2)
            costs_all = all_net_costs(pos)
            cost_now = costs_all.sum()
            # exact best tracking from the freshly re-derived cost (the
            # post-apply cost is only approximate when kept moves share
            # a net, so the best snapshot is taken at step start; the
            # final post-block state is scored at the segment boundary)
            improved = cost_now < best_cost
            best_cost = jnp.where(improved, cost_now, best_cost)
            best_site = jnp.where(improved, site, best_site)
            costs_pad = jnp.concatenate([costs_all, jnp.zeros(1, f32)])
            u = random.uniform(key, (K, 3))
            i = jnp.minimum((u[:, 0] * n).astype(i32), n - 1)
            s = node_off[i] + jnp.minimum(
                (u[:, 1] * node_pool[i]).astype(i32), node_pool[i] - 1)
            j = occ[s]
            old_si = site[i]
            self_move = s == old_si
            j_valid = (j >= 0) & ~self_move
            j_safe = jnp.where(j_valid, j, i)
            # touched nets of the (i, j) pair, j's deduped against i's
            nets_i = node_nets[i]                        # (K, M)
            nets_j = node_nets[j_safe]
            dup_j = (nets_j[:, :, None] == nets_i[:, None, :]).any(-1)
            valid = jnp.concatenate(
                [nets_i >= 0, (nets_j >= 0) & j_valid[:, None] & ~dup_j],
                axis=1)                                  # (K, 2M)
            nets_cat = jnp.concatenate([nets_i, nets_j], axis=1)
            gather_idx = jnp.where(valid, nets_cat, 0)
            old_costs = costs_pad[jnp.where(valid, nets_cat, n_nets)]
            # Eq. 1 on the gathered terminals with i -> s and j -> i's
            # old tile patched in place (no per-proposal position copies)
            terms = term_mat[gather_idx]                 # (K, 2M, D)
            old_pos_i = pos[i]                           # (K, 2)
            new_rc = site_rc[s]
            pts = pos[terms]                             # (K, 2M, D, 2)
            is_i = (terms == i[:, None, None])[..., None]
            is_j = ((terms == j_safe[:, None, None])
                    & j_valid[:, None, None])[..., None]
            pts = jnp.where(is_j, old_pos_i[:, None, None, :], pts)
            pts = jnp.where(is_i, new_rc[:, None, None, :], pts)
            w = pts[..., 1].max(axis=2) - pts[..., 1].min(axis=2)
            h = pts[..., 0].max(axis=2) - pts[..., 0].min(axis=2)
            area = jnp.maximum(
                0, (w + 1) * (h + 1) - term_count[gather_idx])
            new_costs = pow_tab[w + h, area]
            delta = (jnp.where(valid, new_costs - old_costs, 0.0)
                     ).sum(axis=1)
            accept = (~self_move) & ((delta <= 0)
                                     | (u[:, 2] < jnp.exp(-delta / temp)))
            # conflict rule: proposals moving a common node or targeting
            # a common site must not land together (that would corrupt
            # the site bijection); keep an accepted proposal only if no
            # earlier-in-block accepted proposal conflicts with it
            # (strictly triangular, so the block is order-deterministic).
            # Kept moves merely *sharing a net* are allowed: their deltas
            # were scored against the same frozen state (stale-parallel
            # SA), and the full cost is re-derived fresh at the next
            # step anyway.
            ends = jnp.stack([i, j_safe], axis=1)        # (K, 2)
            node_conf = (ends[:, None, :, None]
                         == ends[None, :, None, :]).any((-1, -2))
            conf = node_conf | (s[:, None] == s[None, :])
            earlier = jnp.tril(jnp.ones((K, K), bool), -1)
            kept = accept & ~(conf & earlier & accept[None, :]).any(axis=1)
            # apply the kept set at once; dropped proposals scatter to
            # an out-of-range index (mode="drop")
            im = jnp.where(kept, i, n)
            jm = jnp.where(kept & j_valid, j_safe, n)
            site = site.at[jnp.concatenate([jm, im])].set(
                jnp.concatenate([old_si, s]), mode="drop")
            jv = jnp.where(j_valid, j, -1)
            occ = occ.at[jnp.concatenate([
                jnp.where(kept, old_si, n_slots),
                jnp.where(kept, s, n_slots)])].set(
                jnp.concatenate([jv, i]), mode="drop")
            ev = ev + (~self_move).sum().astype(i32)
            acc = acc + kept.sum().astype(i32)
            return (site, occ, best_cost, best_site, ev, acc, temp), None

        def chain_segment(st, temp, key):
            """One temperature step of one replica: blocks_per_temp
            proposal blocks (per-net costs are re-derived from the site
            assignment at every block, so there is no drifting
            incremental state), then an exact cost for the post-block
            state — the exchange decisions and the best tracker only
            ever see freshly derived costs."""
            site, occ, _, best_cost, best_site, ev, acc = st
            keys = random.split(key, blocks_per_temp)
            carry = (site, occ, best_cost, best_site, ev, acc, temp)
            carry, _ = lax.scan(block_step, carry, keys)
            site, occ, best_cost, best_site, ev, acc, _ = carry
            cost = all_net_costs(site_rc[site]).sum()
            improved = cost < best_cost
            best_cost = jnp.where(improved, cost, best_cost)
            best_site = jnp.where(improved, site, best_site)
            return site, occ, cost, best_cost, best_site, ev, acc

        idx = jnp.arange(replicas)

        def exchange(state, temps, key, phase):
            """Metropolis swap between adjacent temperature-ladder
            slots.  ``phase`` alternates even/odd pairings per segment;
            accepted pairs swap their full chain state (assignment,
            occupancy, best tracker) while the ladder temperatures stay
            with the slots."""
            cost = state[2]
            lead = (idx % 2 == phase) & (idx + 1 < replicas)
            nxt = jnp.minimum(idx + 1, replicas - 1)
            log_a = (1.0 / temps - 1.0 / temps[nxt]) * (cost - cost[nxt])
            u = random.uniform(key, (replicas,))
            swap_up = lead & (jnp.log(u) < log_a)
            swap_dn = jnp.concatenate([jnp.zeros(1, bool), swap_up[:-1]])
            perm = jnp.where(
                swap_up, nxt,
                jnp.where(swap_dn, jnp.maximum(idx - 1, 0), idx))
            return tuple(x[perm] for x in state)

        def segment(carry, seg_i):
            state, temps, key = carry
            key, k_moves, k_swap = random.split(key, 3)
            rkeys = random.split(k_moves, replicas)
            state = jax.vmap(chain_segment)(state, temps, rkeys)
            state = exchange(state, temps, k_swap, seg_i % 2)
            return (state, temps * t_factor, key), None

        (state, _, _), _ = lax.scan(segment, (state, temps, key),
                                    jnp.arange(n_temps))
        return state

    return jax.jit(anneal)


def _flatten_sites(sites: Dict[str, List[Tuple[int, int]]]):
    """Concatenate the per-class site pools into one slot space.

    Returns ``(site_rc, class_off, class_pool)`` — slot ``class_off[c] + k``
    is the k-th site of class ``c``.  IO tiles appear ``IO_CAPACITY`` times
    in the pool (distinct slots, same tile), exactly as in the NumPy path,
    so multi-stream IO capacity is respected by slot bijection alone.
    """
    rc, off, pool = [], {}, {}
    for c in _CLASS_ORDER:
        off[c] = len(rc)
        pool[c] = len(sites[c])
        rc.extend(sites[c])
    return np.asarray(rc, dtype=np.int32), off, pool


def _padded_node_nets(nets, n: int) -> np.ndarray:
    """Per-node incident-net matrix, padded with -1 (sorted rows, like the
    NumPy kernel's ``node_nets``)."""
    max_inc = max((len(nets.node_nets[i]) for i in range(n)), default=1)
    mat = np.full((n, max(1, max_inc)), -1, dtype=np.int32)
    for i in range(n):
        row = nets.node_nets[i]
        mat[i, :len(row)] = row
    return mat


def _probe_temperature(nets, pos0: np.ndarray, node_off: np.ndarray,
                       node_pool: np.ndarray, site_rc: np.ndarray,
                       gamma: float, alpha: float,
                       rng: np.random.Generator) -> float:
    """Initial temperature from the spread of random-move deltas (the same
    heuristic as the NumPy kernel, evaluated on replica 0's start)."""
    from .place import _net_cost_batch

    n = len(pos0)
    n_probe = min(200, 20 * n)
    deltas = []
    for _ in range(n_probe):
        i = int(rng.integers(n))
        s = int(node_off[i] + rng.integers(node_pool[i]))
        touched = nets.node_nets[i]
        if not len(touched):
            continue
        old = _net_cost_batch(pos0, nets.term_mat[touched],
                              nets.term_count[touched], gamma, alpha)
        trial = pos0.copy()
        trial[i] = site_rc[s]
        new = _net_cost_batch(trial, nets.term_mat[touched],
                              nets.term_count[touched], gamma, alpha)
        deltas.append(abs(float(new.sum() - old.sum())))
    return max(1e-3, float(np.std(deltas) if deltas else 1.0) * 10.0)


def anneal_jax(nets, cls: List[str], sites: Dict[str, list], p,
               name: str = "") -> Tuple[np.ndarray, float, dict]:
    """Anneal ``p.replicas`` parallel-tempering chains; return
    ``(best_pos, best_cost, stats)``.

    ``nets`` is the :class:`repro.core.place._Nets` terminal model, ``cls``
    the per-node tile class, ``sites`` the (already region-filtered) site
    pools, ``p`` the :class:`repro.core.place.PlaceParams`.
    """
    import os

    from .config import force_host_device_count

    # apply CASCADE_HOST_DEVICES before jax freezes its backend (no-op —
    # or a warning on mismatch — once jax is live); leave XLA_FLAGS alone
    # when the knob is unset so a hand-set flag survives
    if os.environ.get("CASCADE_HOST_DEVICES"):
        force_host_device_count()
    import jax
    import jax.numpy as jnp
    from jax import random

    n = len(cls)
    devs = jax.devices()
    # size-adaptive ensemble policy: small netlists are cheap to anneal
    # but their single-chain cost is high-variance, so they get more,
    # colder replicas and a doubled ensemble budget; large netlists keep
    # a lean ensemble so the wall-clock win stays large
    small = n <= 150
    replicas = max(1, int(p.replicas if p.replicas is not None
                          else (8 if small else 4)))
    spread = (p.replica_spread if p.replica_spread is not None
              else (0.85 if small else 0.65))
    budget_boost = 2 if small else 1
    if len(devs) > 1 and replicas % len(devs):
        # the replica axis shards across the mesh: round up so every
        # device carries the same number of chains
        replicas += len(devs) - replicas % len(devs)
    K = max(1, int(p.proposal_block))

    with span("cascade.place.setup", replicas=replicas, nodes=n, K=K):
        n_nets = len(nets.nets)
        site_rc, class_off, class_pool = _flatten_sites(sites)
        node_off = np.asarray([class_off[c] for c in cls], dtype=np.int32)
        node_pool = np.asarray([class_pool[c] for c in cls], dtype=np.int32)
        node_nets_mat = _padded_node_nets(nets, n)
        n_slots = len(site_rc)

        # --- per-replica initial states (seed-derived, replica-salted) ---
        site0 = np.zeros((replicas, n), dtype=np.int32)
        occ0 = np.full((replicas, n_slots), -1, dtype=np.int32)
        for r in range(replicas):
            rs = np.random.default_rng([int(p.seed), r])
            for c in _CLASS_ORDER:
                members = [i for i in range(n) if cls[i] == c]
                if not members:
                    continue
                chosen = rs.choice(class_pool[c], size=len(members),
                                   replace=False)
                for i, k in zip(members, chosen):
                    s = class_off[c] + int(k)
                    site0[r, i] = s
                    occ0[r, s] = i

        from .place import _net_cost_batch
        pos0 = site_rc[site0[0]].astype(np.int64)
        cost0 = np.asarray([
            _net_cost_batch(site_rc[site0[r]].astype(np.int64), nets.term_mat,
                            nets.term_count, p.gamma, p.alpha).sum()
            for r in range(replicas)], dtype=np.float32)

        base_temp = _probe_temperature(
            nets, pos0, node_off, node_pool, site_rc,
            p.gamma, p.alpha, np.random.default_rng(p.seed))
        # geometric ladder: slot 0 anneals the NumPy schedule, higher slots
        # run hotter so exchanges can tunnel out of local minima
        temps0 = base_temp * (spread ** np.arange(replicas))

        # every replica evaluates the full NumPy move budget; the speedup
        # comes from evaluating K proposals per sequential step, not from
        # shortening the anneal
        total_moves = budget_boost * p.moves_per_node * max(n, 16)
        n_temps = max(1, int(math.log(5e-4) / math.log(p.t_factor)))
        blocks_per_temp = max(1, total_moves // n_temps // K)

        hmax = int(site_rc[:, 0].max() - site_rc[:, 0].min())
        wmax = int(site_rc[:, 1].max() - site_rc[:, 1].min())
        pow_tab = np.power(
            np.arange(hmax + wmax + 1, dtype=np.float64)[:, None]
            + p.gamma * np.arange((hmax + 1) * (wmax + 1) + 1,
                                  dtype=np.float64)[None, :],
            p.alpha).astype(np.float32)
        tables = (jnp.asarray(site_rc), jnp.asarray(node_off),
                  jnp.asarray(node_pool), jnp.asarray(node_nets_mat),
                  jnp.asarray(nets.term_mat.astype(np.int32)),
                  jnp.asarray(nets.term_count.astype(np.int32)),
                  jnp.asarray(pow_tab))
        state = (jnp.asarray(site0), jnp.asarray(occ0), jnp.asarray(cost0),
                 jnp.asarray(cost0),                     # best_cost
                 jnp.asarray(site0),                     # best_site
                 jnp.zeros(replicas, dtype=jnp.int32),   # evaluated
                 jnp.zeros(replicas, dtype=jnp.int32))   # accepted
        temps = jnp.asarray(temps0.astype(np.float32))
        if len(devs) > 1:
            # shard the replica axis across the host mesh (the tables are
            # replicated by XLA)
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            mesh = Mesh(np.asarray(devs), ("r",))
            state = tuple(
                jax.device_put(x, NamedSharding(
                    mesh, P("r", *([None] * (x.ndim - 1)))))
                for x in state)

    with span("cascade.place.anneal", replicas=replicas, nodes=n, K=K):
        anneal = _jitted_anneal(n, n_nets, n_slots, replicas, K,
                                n_temps, blocks_per_temp)
        out = anneal(tables, state, temps, random.PRNGKey(int(p.seed)),
                     jnp.float32(p.t_factor))
        best_costs = np.asarray(out[3], dtype=np.float64)
        best_r = int(best_costs.argmin())
        best_pos = site_rc[np.asarray(out[4][best_r])].astype(np.int64)
        evaluated = int(np.asarray(out[5]).sum())
        accepted = int(np.asarray(out[6]).sum())

    # re-derive the winning cost in float64 through the NumPy Eq. 1 kernel
    # so cross-backend cost comparisons are apples to apples
    best_cost = float(_net_cost_batch(best_pos, nets.term_mat,
                                      nets.term_count, p.gamma,
                                      p.alpha).sum())
    stats = {
        "replicas": replicas,
        "devices": len(devs),
        "proposal_block": K,
        "moves_evaluated": evaluated,
        "moves_accepted": accepted,
        "resyncs": int(n_temps * blocks_per_temp),
        "best_replica": best_r,
        "replica_costs": [round(float(c), 3) for c in best_costs],
    }
    return best_pos, best_cost, stats
