"""Cascade-guided pipeline-stage partitioning (beyond-paper bridge).

The paper's post-PnR pipelining loop is: find the critical combinational
segment with STA, break it by enabling a register, re-balance, repeat until
no segment improves.  At cluster scale the same loop solves pipeline-
parallel stage partitioning: layers are "combinational elements" whose delay
is their per-chip roofline time, a stage boundary is a "pipeline register"
whose cost is the activation transfer over ICI/DCI, and the clock period is
the pipeline beat (the slowest stage).  1F1B fill/drain bubbles play the
role of pipeline fill latency.

``partition(...)`` runs exactly that loop:

  1. start with one segment (all layers combinational);
  2. STA = segment delays (max-plus over the chain);
  3. break the worst segment at its weighted median — the register-insertion
     step — while the added boundary pays for itself (beat shrinks);
  4. stop at the stage budget, or when three consecutive breaks improve the
     beat by <5% (the paper's §V-D stopping rule).

Compared to the naive contiguous equal-layer split, this balances
heterogeneous stacks (MoE interleave, hybrid shared-attention) by cost, not
by count.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

from repro.configs.base import ModelConfig, ShapeSpec
from repro.distributed.peaks import TPU_V5E, peaks_for

#: stages are planned for v5e pods
PEAK = peaks_for(TPU_V5E)


# ---------------------------------------------------------------------------
# per-layer roofline delays


def layer_costs(cfg: ModelConfig, shape: ShapeSpec, chips_per_stage: int,
                microbatches: int = 8) -> List[float]:
    """Per-layer per-microbatch step time (s) on `chips_per_stage` chips:
    max(compute, memory) roofline term of one layer."""
    tokens = shape.seq_len * shape.global_batch / microbatches
    d, hd = cfg.d_model, cfg.resolved_head_dim
    fwd_bwd = 3.0 if shape.kind == "train" else 1.0

    def t(flops, bytes_):
        return max(flops / (chips_per_stage * PEAK.flops),
                   bytes_ / (chips_per_stage * PEAK.hbm_bw))

    out: List[float] = []
    for li in range(cfg.num_layers):
        attn_p = cfg._attn_params(d, cfg.num_heads, cfg.num_kv_heads, hd)
        if cfg.family in ("ssm", "hybrid"):
            p = (cfg._rwkv_layer_params() if cfg.family == "ssm"
                 else cfg._mamba_layer_params())
            fl = 2 * p * tokens * fwd_bwd
            by = 2 * p + tokens * d * 2 * 6
            if cfg.family == "hybrid" and li in cfg.hybrid_layers:
                ap = cfg._shared_block_params() + \
                    cfg._hybrid_invocation_params()
                fl += 2 * ap * tokens * fwd_bwd + \
                    4 * tokens * shape.seq_len * cfg.num_heads * hd * 0.5
                by += 2 * ap
        elif cfg.num_experts and (li % cfg.moe_layer_period ==
                                  cfg.moe_layer_period - 1):
            active = attn_p + cfg.experts_per_token * \
                cfg._mlp_params(d, cfg.d_ff) * cfg.capacity_factor
            fl = 2 * active * tokens * fwd_bwd + \
                4 * tokens * shape.seq_len * cfg.num_heads * hd * 0.5 * fwd_bwd
            # MoE reads ALL resident expert weights per step: memory-heavy
            by = 2 * (attn_p + cfg.num_experts * cfg._mlp_params(d, cfg.d_ff)
                      / max(1, chips_per_stage)) + tokens * d * 2 * 8
        else:
            p = attn_p + cfg._mlp_params(
                d, cfg.d_ff, gated=cfg.family != "audio")
            fl = 2 * p * tokens * fwd_bwd + \
                4 * tokens * shape.seq_len * cfg.num_heads * hd * 0.5 * fwd_bwd
            by = 2 * p + tokens * d * 2 * 8
        out.append(t(fl, by))
    return out


def boundary_cost(cfg: ModelConfig, shape: ShapeSpec, microbatches: int,
                  chips_per_stage: int) -> float:
    """Activation transfer time across one stage boundary (per microbatch)."""
    tokens = shape.seq_len * shape.global_batch / microbatches
    act_bytes = tokens * cfg.d_model * 2
    return act_bytes / (chips_per_stage * PEAK.ici_bw)


# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PipelinePlan:
    boundaries: List[int]            # stage i = layers [b[i], b[i+1])
    beat_s: float                    # slowest stage+boundary time
    makespan_s: float                # (M + S - 1) * beat (1F1B)
    bubble_frac: float
    stage_times: List[float]
    history: List[Tuple[int, float]]  # (n_stages, beat) per iteration


def _stage_times(costs: Sequence[float], bounds: List[int],
                 bcost: float) -> List[float]:
    out = []
    for i in range(len(bounds) - 1):
        seg = sum(costs[bounds[i]:bounds[i + 1]])
        out.append(seg + (bcost if i + 1 < len(bounds) - 1 else 0.0))
    return out


def _refine(costs: Sequence[float], bounds: List[int], bcost: float
            ) -> List[int]:
    """Re-balancing after the register insertions (the Cascade matching
    step): the same number of stages, their boundaries placed for the
    lowest beat.  An exact min-max split of the chain: sliding one
    boundary at a time stalls where a move helps only once its neighbour
    has moved too."""
    n, k = len(costs), len(bounds) - 1
    pre = np.concatenate([[0.0], np.cumsum(costs)])
    # best[j][i]: the lowest beat of costs[:i] in j non-final stages
    best = [[0.0] + [math.inf] * n]
    back: List[List[int]] = [[0] * (n + 1)]
    for j in range(1, k):
        row, arg = [math.inf] * (n + 1), [0] * (n + 1)
        for i in range(j, n - (k - j) + 1):
            for p in range(j - 1, i):
                v = max(best[j - 1][p], pre[i] - pre[p] + bcost)
                if v < row[i]:
                    row[i], arg[i] = v, p
        best.append(row)
        back.append(arg)
    cut = min(range(k - 1, n),
              key=lambda p: max(best[k - 1][p], pre[n] - pre[p]))
    out = [n]
    for j in range(k - 1, 0, -1):
        out.append(cut)
        cut = back[j][cut]
    return [0] + out[::-1]


def partition(costs: Sequence[float], num_stages: int, bcost: float,
              microbatches: int = 8, improve_eps: float = 0.05
              ) -> PipelinePlan:
    """Cascade post-PnR loop over the layer chain."""
    n = len(costs)
    bounds = [0, n]
    history: List[Tuple[int, float]] = []
    stale = 0
    while len(bounds) - 1 < num_stages and stale < 3:
        times = _stage_times(costs, bounds, bcost)
        beat = max(times)
        history.append((len(bounds) - 1, beat))
        # critical segment = the paper's critical path
        wi = int(np.argmax(times))
        lo, hi = bounds[wi], bounds[wi + 1]
        if hi - lo < 2:
            break
        # break near the weighted median (balanced register insertion):
        # evaluate the median cut and its neighbours, keep the best —
        # alternating-cost stacks (MoE interleave) make the raw median
        # overshoot by one
        seg = list(costs[lo:hi])
        csum = np.cumsum(seg)
        med = lo + 1 + int(np.searchsorted(csum, csum[-1] / 2))
        best_cut, best_val = None, None
        for cut in (med - 1, med, med + 1):
            cut = min(max(cut, lo + 1), hi - 1)
            val = max(sum(costs[lo:cut]), sum(costs[cut:hi]))
            if best_val is None or val < best_val:
                best_cut, best_val = cut, val
        new_bounds = sorted(set(bounds + [best_cut]))
        new_beat = max(_stage_times(costs, new_bounds, bcost))
        if new_beat >= beat * (1 - improve_eps):
            stale += 1
        else:
            stale = 0
        bounds = new_bounds
    bounds = _refine(costs, bounds, bcost)
    times = _stage_times(costs, bounds, bcost)
    beat = max(times)
    s = len(bounds) - 1
    makespan = (microbatches + s - 1) * beat
    ideal = sum(costs)
    return PipelinePlan(
        boundaries=bounds, beat_s=beat, makespan_s=makespan,
        bubble_frac=(s - 1) / (microbatches + s - 1),
        stage_times=times, history=history)


def naive_partition(costs: Sequence[float], num_stages: int, bcost: float,
                    microbatches: int = 8) -> PipelinePlan:
    """Contiguous equal-LAYER-count split (the baseline every framework
    ships)."""
    n = len(costs)
    bounds = [round(i * n / num_stages) for i in range(num_stages + 1)]
    bounds = sorted(set(bounds))
    times = _stage_times(costs, bounds, bcost)
    beat = max(times)
    s = len(bounds) - 1
    return PipelinePlan(
        boundaries=bounds, beat_s=beat,
        makespan_s=(microbatches + s - 1) * beat,
        bubble_frac=(s - 1) / (microbatches + s - 1),
        stage_times=times, history=[])


def plan_for(cfg: ModelConfig, shape: ShapeSpec, num_stages: int = 4,
             chips_per_stage: int = 64, microbatches: int = 8
             ) -> Dict[str, PipelinePlan]:
    costs = layer_costs(cfg, shape, chips_per_stage, microbatches)
    bc = boundary_cost(cfg, shape, microbatches, chips_per_stage)
    return {
        "cascade": partition(costs, num_stages, bc, microbatches),
        "naive": naive_partition(costs, num_stages, bc, microbatches),
    }
