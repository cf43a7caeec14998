"""Serve a language model in static batches, through the program's own
prefill and decode steps, and check the served tokens against the plain
float32 reference.

The traffic file gives the batch shapes (``shapes``: batch, prompt and
generated tokens), the order of one cycle of batches (``order``, indices
into ``shapes``, repeated), and the arrivals: ``batch_interval_s`` 0 is a
closed loop (the next batch starts when the last one has finished), a
positive value is an open loop with a batch due every that many seconds,
timed from when it was due.  Every seed serves the same sizes at the same
times; prompts are uniform random tokens from the seed.

Each step's tokens are brought to the host as they come (a streaming
server must), while the next step is already queued on the device, and
are timed by when they arrive.  A token counts if it arrived inside the
window.
"""

from __future__ import annotations

import gc
from typing import Dict, List

import numpy as np

from bench.lib import flops as F
from bench.lib.harness import (Cell, Tracer, device_info, now,
                               setup_note, span)

#: configuration keys (Hugging Face names) -> the program's ModelConfig
MODEL_FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "num_local_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "capacity_factor": "capacity_factor",
    "dtype": "dtype", "use_flash": "use_flash",
}


def reference(dims: dict):
    """The configuration's plain reference (``bench/lib/<reference>.py``):
    it makes the weights and judges the served tokens."""
    import importlib
    return importlib.import_module("bench.lib." + dims["reference"])


def model_config(dims: dict):
    from repro.configs import get_config
    return get_config(dims["arch"]).replace(
        **{f: dims[k] for k, f in MODEL_FIELDS.items()})


class Server:
    """The program's serve path, built once: weights, a cache per shape,
    and compiled prefill and decode steps that return greedy tokens."""

    def __init__(self, cell: Cell):
        import jax
        import jax.numpy as jnp
        from repro.configs import ShapeSpec
        from repro.distributed import sharding as shd
        from repro.launch import steps as S
        from repro.launch.mesh import make_mesh_for
        from repro.models import LM

        self.dims = cell.config
        cfg = model_config(self.dims)
        self.vocab = cfg.vocab_size
        model = LM(cfg)
        shd.set_rules(S.rules_for(cfg))
        self.mesh = make_mesh_for(cell.chips)
        jax.sharding.set_mesh(self.mesh)
        shapes = [tuple(s) for s in cell.traffic["shapes"]]
        specs = {s: ShapeSpec("serve", s[1] + s[2], s[0], "decode")
                 for s in shapes}
        p_sh = S.serve_shardings(model, self.mesh, specs[shapes[0]])[0]

        self._want = jax.tree.map(lambda a: (a.shape, a.dtype),
                                  model.shapes())
        self._p_sh = p_sh
        self.reweight(cell.seed)

        vocab, fault = self.vocab, cell.fault
        pstep, dstep = S.make_prefill_step(model), S.make_decode_step(model)

        def prefill(params, tokens, cache):
            logits, cache = pstep(params, {"tokens": tokens}, cache)
            return jnp.argmax(logits[:, :vocab], -1).astype(jnp.int32), cache

        def decode(params, toks, cache, pos):
            logits, new = dstep(params, {"tokens": toks[:, None]}, cache, pos)
            out = jnp.argmax(logits[:, :vocab], -1).astype(jnp.int32)
            if fault == "token_altered":
                out = out.at[:].set((out + 1) % vocab)
            return out, (cache if fault == "state_unchanged" else new)

        self.caches, self.prefill, self.decode = {}, {}, {}
        for s in shapes:
            b, p, g = s
            c_sh = S.serve_shardings(model, self.mesh, specs[s])[2]
            self.caches[s] = jax.jit(lambda: model.init_cache(b, p + g),
                                     out_shardings=c_sh)()
            toks = jnp.zeros((b, p), jnp.int32)
            self.prefill[s] = jax.jit(prefill, donate_argnums=(2,)).lower(
                self.params, toks, self.caches[s]).compile()
            self.decode[s] = jax.jit(decode, donate_argnums=(2,)).lower(
                self.params, toks[:, 0], self.caches[s],
                jnp.int32(p)).compile()

    def reweight(self, seed: int) -> None:
        """Weights from ``seed``, checked against the serve path's tree."""
        import jax
        self.params = None
        self.params = reference(self.dims).make_weights(self.dims, seed,
                                                        self._p_sh)
        got = jax.tree.map(lambda a: (a.shape, a.dtype), self.params)
        if got != self._want:
            raise ValueError(f"weights do not match the serve path's tree: "
                             f"{got} != {self._want}")

    def serve(self, shape, prompts: np.ndarray, due: float, stop):
        """Serve one batch; returns its tokens [B, G] and the arrival time
        of each step.  ``stop(t)`` is asked after each arrival and ends
        the batch early when true (the rest is not served)."""
        import jax.numpy as jnp
        b, p, g = shape
        cache = self.caches.pop(shape)
        toks = np.zeros((b, g), np.int32)
        arrive: List[float] = []
        with span("prefill"):
            prev, cache = self.prefill[shape](self.params,
                                              jnp.asarray(prompts), cache)
        prev.copy_to_host_async()
        for i in range(1, g + 1):
            nxt = None
            if i < g:
                with span("decode"):
                    nxt, cache = self.decode[shape](self.params, prev, cache,
                                                    jnp.int32(p + i - 1))
                nxt.copy_to_host_async()
            with span("fetch"):
                toks[:, i - 1] = np.asarray(prev)
            arrive.append(now())
            if nxt is None or stop(arrive[-1]):
                if nxt is not None:
                    nxt.block_until_ready()
                break
            prev = nxt
        self.caches[shape] = cache
        return toks, arrive


def _cycle(traffic: dict) -> List[tuple]:
    """One cycle of batch shapes, in the traffic's fixed ``order`` (indices
    into ``shapes``): every seed serves the same sizes at the same times."""
    return [tuple(traffic["shapes"][i]) for i in traffic["order"]]


def _step_work(dims, shape, step: int) -> dict:
    """Model FLOPs and flash_decode cost of step ``step`` of a batch (0 is
    the prefill)."""
    b, p, _ = shape
    if step == 0:
        return {"flops": F.prefill_flops(dims, b, p), "kernel": None}
    length = p + step                     # keys the new token attends to
    kv, h, hd = (dims["num_key_value_heads"], dims["num_attention_heads"],
                 dims["head_dim"])
    kern = F.flash_decode_cost(b, kv, h // kv, hd, [length] * b)
    return {"flops": F.decode_step_flops(dims, b, length),
            "kernel": kern if dims.get("use_flash") else None}


def run(cell: Cell, server: "Server" = None, control: bool = False) -> dict:
    """One run of the cell.  ``server`` reuses a built serve path under
    new weights from the cell's seed, and ``control`` also reads the fp8
    control's gaps on the same requests (both for bench/control.py)."""
    import sys
    import time
    import jax
    tr = cell.traffic
    rng = np.random.default_rng(cell.seed)
    if server is None:
        server = Server(cell)
    else:
        server.reweight(cell.seed)
    dims, vocab = server.dims, server.vocab
    t_warm = now()

    # warm-up: every shape once, through prefill and two decode steps
    for s in server.prefill:
        arrivals = []
        server.serve(s, rng.integers(0, vocab, s[:2]).astype(np.int32),
                     now(), lambda t: arrivals.append(t) or len(arrivals) > 2)
    jax.effects_barrier()
    setup_s = now() - cell.t_process
    setup_note(cell, setup_s, t_warm, "warm-up")

    tracer = Tracer(cell)
    interval = float(tr["batch_interval_s"])
    requests: List[dict] = []             # every batch served, in order
    before = cell.meter.snapshot()
    t_start = now()
    deadline = t_start + cell.seconds
    trace_at = t_start + tr.get("trace_after_s", 0.0)

    def stop(t):
        """Asked at each arrival: trace a stretch of the window, and end
        the batch once the window has closed, unless no batch has finished
        yet (then it finishes, untimed, for the check)."""
        if tracer.on and tracer.t0 is None and t >= trace_at:
            tracer.start()
        if tracer.active and t >= tracer.t0 + tr["trace_seconds"]:
            tracer.stop()
        return t > deadline and any(r["complete"] for r in requests)

    cycle: List[tuple] = []
    k = 0
    while now() <= deadline:
        due = t_start + k * interval if interval > 0 else now()
        if now() < due:
            with span("wait"):            # open loop: the next batch is due
                time.sleep(due - now())
        if not cycle:
            cycle = _cycle(tr)
        shape = cycle.pop(0)
        prompts = rng.integers(0, vocab, shape[:2]).astype(np.int32)
        begin = now()
        toks, arrive = server.serve(shape, prompts, due, stop)
        requests.append({"shape": shape, "prompts": prompts, "tokens": toks,
                         "due": due, "begin": begin, "arrive": arrive,
                         "complete": len(arrive) == shape[2]})
        k += 1
    tracer.stop()
    in_window = cell.meter.since(before)

    # end-to-end metrics: tokens and first tokens that reached the host
    tokens, ttft = 0, []
    for r in requests:
        b = r["shape"][0]
        tokens += b * sum(1 for t in r["arrive"] if t <= deadline)
        if r["arrive"][0] <= deadline:
            ttft += [r["arrive"][0] - r["due"]] * b
    e2e = {"decode_tok_s": tokens / cell.seconds,
           "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95))}

    # work per step, by arrival time, for the per-layer readers
    work = [{"t": t, **_step_work(dims, r["shape"], i)}
            for r in requests for i, t in enumerate(r["arrive"])]
    device = device_info(cell.chips)
    red = tracer.reduce()

    # free the program's state, then the reference over a sample
    if not control:
        server.caches.clear()
        server.prefill.clear()
        server.decode.clear()
        gc.collect()
    checks = check(server.params, dims, requests, tr, cell.seed, control)

    late = [r["arrive"][0] - r["due"] for r in requests] if interval else []
    for shape in map(tuple, tr["shapes"]):
        rs = [r for r in requests if r["shape"] == shape and r["complete"]]
        if rs:
            print(f"shape {list(shape)}: {len(rs)} batches, first token "
                  f"{np.mean([r['arrive'][0] - r['begin'] for r in rs]):.4f}"
                  f" s, batch {np.mean([r['arrive'][-1] - r['begin'] for r in rs]):.4f} s after it began",
                  file=sys.stderr)
    print(f"window {cell.seconds} s: batches {len(requests)}, tokens "
          f"{tokens}, requests with a first token {len(ttft)}, xla compiles "
          f"{in_window['xla_compiles']} ({in_window['xla_compile_s']:.3f} s)"
          + (f", first token after due: max {max(late):.4f} s" if late
             else ""), file=sys.stderr)
    return {"setup_s": setup_s, "e2e": e2e, "checks": checks,
            "attempted": len(ttft), "failed": 0, "device": device,
            "trace": red,
            "records": {"work": work, "trace": red, "dims": dims,
                        "tracer": (tracer.t0, tracer.t1),
                        "device_kind": device["kind"]},
            "requests": requests}


def gap_stats(gaps: np.ndarray) -> dict:
    """The widest and the mean gap, the share of served tokens that are
    not the reference's argmax (gap above 0), and the share whose gap
    passes 0.1: beyond bfloat16's rounding at a near-tie, within what a
    lower precision's errors reach."""
    if not np.all(np.isfinite(gaps)):
        gaps = np.full_like(gaps, np.inf)
    return {"max_logit_gap": float(np.max(gaps)),
            "mean_logit_gap": float(np.mean(gaps)),
            "off_argmax_share": float(np.mean(gaps > 0)),
            "share_gap_over_0.1": float(np.mean(gaps > 0.1))}


def check(params, dims: dict, requests: List[dict], tr: dict,
          seed: int, control: bool = False) -> Dict[str, tuple]:
    """Over a sample of finished requests drawn from the seed (one of the
    longest shape first), how far each served token's logit lies below the
    reference's best logit at its position (``gap_stats``); the traffic's
    ``limits`` name the numbers compared.  With ``control`` the fp8
    control's numbers on the same requests come too."""
    ref = reference(dims)
    rng = np.random.default_rng([seed, 1])
    pool = [(r, j) for r in requests if r["complete"]
            for j in range(r["shape"][0])]
    longest = max(r["shape"][1] for r, _ in pool)
    first = [x for x in pool if x[0]["shape"][1] == longest]
    picks = [first[rng.integers(len(first))]]
    rest = [x for x in pool if x is not picks[0]]
    n = min(tr["check_requests"] - 1, len(rest))
    picks += [rest[i] for i in rng.choice(len(rest), n, replace=False)]
    gaps, lows = [], []
    for r, j in picks:
        g = ref.served_gaps(params, dims, r["prompts"][j], r["tokens"][j],
                            control=control)
        gaps.append(g["gaps"])
        if control:
            lows.append(g["control_gaps"])
    gaps = np.concatenate(gaps)
    stats = gap_stats(gaps)
    import sys
    print("served-token gaps: " + ", ".join(f"{k} {v}" for k, v in
                                             stats.items()), file=sys.stderr)
    lim = tr["limits"]
    out = {k: (stats[k], lim[k]) for k in lim}
    out["served_tokens_short"] = (max(0, tr["check_tokens"] - len(gaps)), 0)
    if control:
        out.update({k: (v, lim.get(k)) for k, v in stats.items()})
        for k, v in gap_stats(np.concatenate(lows)).items():
            out["control_" + k] = (v, lim.get(k))
    return out
