"""LM — one model class covering every assigned architecture family.

Families and their block stacks:

  dense   (llama3 / qwen2.5 / minicpm / mistral-large): scan over L identical
          pre-norm blocks (GQA attention + SwiGLU MLP).
  moe     (granite / llama4-maverick): groups of (period-1) dense layers + 1
          MoE layer, two-level scan.
  ssm     (rwkv6): scan over RWKV6 time-mix/channel-mix layers.
  hybrid  (zamba2): one scan over the Mamba2 layers; before each layer of
          ``hybrid_layer_ids`` one of ``shared_blocks`` SHARED transformer
          blocks (used in turn; their weights are not stacked) runs, and its
          output joins that layer's input (zamba-style weight sharing).
  vlm     (llama-3.2-vision): groups of self-attention layers with a
          cross-attention block (into stub image embeddings) per group.
  audio   (whisper): encoder scan (bidirectional) + decoder scan
          (causal self + cross into encoder memory); conv frontend is a stub
          (precomputed frame embeddings), per the assignment.

Everything is scan-over-layers with stacked parameters, so HLO size is
independent of depth; remat policy wraps the scanned body.

The same forward code serves three entry points:
  ``loss``         — training loss (next-token xent + z-loss + MoE aux)
  ``prefill``      — forward + KV-cache/state fill, returns last logits
  ``decode_step``  — single-token step against the cache (serve_step)
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from . import layers as Lyr
from . import ssm as Ssm
from .params import (ParamDef, Tree, init_params, param_logical_axes,
                     param_shapes)


def _stack_reshape(tree: Tree, groups: int, per: int) -> Tree:
    """[L, ...] stacked params -> [groups, per, ...]."""
    return jax.tree.map(
        lambda x: x.reshape((groups, per) + x.shape[1:]), tree)


def scan_layers(f, init, xs, *, unroll: bool = False):
    """lax.scan over stacked layer params — or a python-unrolled loop when
    ``unroll`` (ModelConfig.scan_layers=False).  The unrolled form exists for
    the dry-run cost probes: XLA's cost analysis counts a while body once, so
    unrolled probe modules give trip-count-exact FLOP/byte/collective counts
    that are extrapolated to full depth."""
    if not unroll:
        return jax.lax.scan(f, init, xs)
    length = jax.tree.leaves(xs)[0].shape[0]
    carry, ys = init, []
    for i in range(length):
        xi = jax.tree.map(lambda a: a[i], xs)
        carry, y = f(carry, xi)
        ys.append(y)
    if ys and jax.tree.leaves(ys[0]):
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


def _maybe_remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    def _scan(self, f, init, xs):
        return scan_layers(f, init, xs, unroll=not self.cfg.scan_layers)

    def _impl(self, s: int) -> str:
        """Attention implementation for a query length of s."""
        cfg = self.cfg
        if cfg.attn_impl != "auto":
            return cfg.attn_impl
        if cfg.use_flash and s > 1:
            return "flash"
        return "blockwise" if s >= 4096 else "einsum"

    # ------------------------------------------------------------------
    # parameter definitions

    def param_defs(self) -> Tree:
        cfg = self.cfg
        L = cfg.num_layers
        defs: Tree = {"embed": Lyr.embed_defs(cfg),
                      "final_norm": Lyr.norm_defs(
                          cfg.d_model, with_bias=cfg.family == "audio")}
        fam = cfg.family
        if fam == "ssm":
            defs["blocks"] = Ssm.rwkv_defs(cfg, L)
        elif fam == "hybrid":
            defs["blocks"] = Ssm.mamba_defs(cfg, L)
            defs["shared"] = {f"block{k}": self._shared_block_defs()
                              for k in range(cfg.shared_blocks)}
            n_inv = len(cfg.hybrid_layers)
            own: Tree = {}
            if cfg.adapter_rank:
                own["adapter"] = Lyr.adapter_defs(cfg, n_inv)
            if cfg.hybrid_linear:
                d = cfg.d_model
                own["linear"] = ParamDef((n_inv, d, d),
                                         ("layers", "embed", "mlp"))
            if own:
                defs["hybrid"] = own
        elif fam == "audio":
            enc = cfg.encoder_layers or L
            defs["encoder"] = self._dense_block_defs(
                layers=enc, gated=False, with_bias=True)
            defs["blocks"] = self._dense_block_defs(
                layers=L, gated=False, with_bias=True, cross=True)
            defs["enc_final_norm"] = Lyr.norm_defs(cfg.d_model, with_bias=True)
        elif fam == "vlm":
            defs["blocks"] = self._dense_block_defs(layers=L)
            n_cross = L // cfg.cross_attn_every
            # llama3.2-style cross layers: cross-attn + MLP, no self-attn
            defs["cross_blocks"] = self._dense_block_defs(
                layers=n_cross, cross=True, cross_only=True)
        elif fam == "moe":
            period = cfg.moe_layer_period
            n_moe = L // period
            if period > 1:
                defs["blocks"] = self._dense_block_defs(layers=L - n_moe)
            defs["moe_blocks"] = self._dense_block_defs(layers=n_moe, moe=True)
        else:
            defs["blocks"] = self._dense_block_defs(layers=L)
        return defs

    def _dense_block_defs(self, layers: int, gated: bool = True,
                          with_bias: bool = False, moe: bool = False,
                          cross: bool = False, cross_only: bool = False
                          ) -> Tree:
        cfg = self.cfg
        d = cfg.d_model
        out = {
            "ln2": Lyr.norm_defs(d, with_bias, (layers,) if layers else ()),
        }
        if not cross_only:
            out["ln1"] = Lyr.norm_defs(d, with_bias,
                                       (layers,) if layers else ())
            out["attn"] = Lyr.attention_defs(cfg, layers=layers)
        if moe:
            out["ffn"] = Lyr.moe_defs(cfg, layers=layers)
        else:
            out["ffn"] = Lyr.mlp_defs(cfg, gated=gated, layers=layers)
        if cross:
            out["ln_x"] = Lyr.norm_defs(d, with_bias,
                                        (layers,) if layers else ())
            out["xattn"] = Lyr.attention_defs(cfg, layers=layers)
        return out

    def _shared_block_defs(self) -> Tree:
        """A zamba2 shared block: norm, attention over the block's input
        (concat(hidden, embeddings) with ``shared_concat``), norm, gated
        MLP; no residual of its own."""
        cfg = self.cfg
        d = cfg.d_model
        d_in = d * (2 if cfg.shared_concat else 1)
        return {"ln1": Lyr.norm_defs(d_in),
                "attn": Lyr.attention_defs(cfg, d_in=d_in),
                "ln2": Lyr.norm_defs(d),
                "ffn": Lyr.mlp_defs(cfg)}

    def init(self, rng: jax.Array) -> Tree:
        return init_params(rng, self.param_defs())

    def shapes(self) -> Tree:
        return param_shapes(self.param_defs())

    def logical_axes(self) -> Tree:
        return param_logical_axes(self.param_defs())

    # ------------------------------------------------------------------
    # block appliers (p = one layer's param slice)

    def _dense_block(self, p: Tree, x, positions, *, impl, causal=True,
                     memory=None, cache=None, cache_pos=None,
                     cache_layer=None, xmemory_kv=None):
        cfg = self.cfg
        new_cache = None
        if "attn" in p:
            h = Lyr.apply_norm(p["ln1"], x, cfg.norm_eps)
            a, new_cache = Lyr.attention(
                p["attn"], h, cfg, positions=positions, causal=causal,
                cache=cache, cache_pos=cache_pos, cache_layer=cache_layer,
                impl=impl)
            x = x + a
        aux = jnp.zeros((), jnp.float32)
        if "xattn" in p:
            h = Lyr.apply_norm(p["ln_x"], x, cfg.norm_eps)
            if xmemory_kv is not None:       # decode: precomputed cross K/V
                xa = self._cross_from_kv(p["xattn"], h, xmemory_kv)
            else:
                xa, _ = Lyr.attention(p["xattn"], h, cfg, positions=positions,
                                      causal=False, memory=memory,
                                      impl="einsum")
            x = x + xa
        h = Lyr.apply_norm(p["ln2"], x, cfg.norm_eps)
        if "router" in p["ffn"]:
            m, aux = Lyr.moe_ffn(p["ffn"], h, cfg)
        else:
            m = Lyr.mlp(p["ffn"], h)
        return x + m, new_cache, aux

    def _cross_from_kv(self, p: Tree, x, kv: Tree) -> jax.Array:
        """Cross-attention against precomputed K/V [B, KV, T, hd]."""
        cfg = self.cfg
        b, s, _ = x.shape
        hd = cfg.resolved_head_dim
        hq, hkv = cfg.num_heads, cfg.num_kv_heads
        q = (x @ p["wq"])
        if "bq" in p:
            q = q + p["bq"]
        q = q.reshape(b, s, hkv, hq // hkv, hd)
        k = jnp.moveaxis(kv["k"], 1, 2)
        v = jnp.moveaxis(kv["v"], 1, 2)
        out = Lyr._einsum_attention(q, k, v, causal=False)
        return out.reshape(b, s, hq * hd) @ p["wo"]

    def _cross_kv(self, p: Tree, memory: jax.Array) -> Tree:
        """Precompute cross K/V from memory for decode."""
        cfg = self.cfg
        b, t, _ = memory.shape
        hd, hkv = cfg.resolved_head_dim, cfg.num_kv_heads
        k = memory @ p["wk"]
        v = memory @ p["wv"]
        if "bk" in p:
            k, v = k + p["bk"], v + p["bv"]
        k = jnp.moveaxis(k.reshape(b, t, hkv, hd), 1, 2)
        v = jnp.moveaxis(v.reshape(b, t, hkv, hd), 1, 2)
        return {"k": k, "v": v}

    # ------------------------------------------------------------------
    # forward (training / no-cache)

    def forward(self, params: Tree, batch: Dict[str, jax.Array]
                ) -> Tuple[jax.Array, jax.Array]:
        """Returns (logits [B,S,V], moe_aux)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        x = Lyr.embed(params["embed"], tokens)
        impl = self._impl(s)
        aux_total = jnp.zeros((), jnp.float32)

        fam = cfg.family
        if fam == "ssm":
            def body(x, p):
                y, _ = Ssm.rwkv_block(p, x, cfg)
                return y, None
            x, _ = self._scan(_maybe_remat(body, cfg.remat),
                                x, params["blocks"])
        elif fam == "hybrid":
            x, _ = self._hybrid_stack(params, x, positions, impl=impl)
        elif fam == "audio":
            x, aux_total = self._audio_forward(params, batch, x, positions,
                                               impl)
        elif fam == "vlm":
            x, aux_total = self._vlm_forward(params, batch, x, positions,
                                             impl)
        elif fam == "moe":
            x, aux_total = self._moe_forward(params, x, positions, impl)
        else:
            def body(x, p):
                y, _, aux = self._dense_block(p, x, positions, impl=impl)
                return y, aux
            x, auxs = self._scan(_maybe_remat(body, cfg.remat),
                                   x, params["blocks"])
            aux_total = jnp.sum(auxs)

        x = Lyr.apply_norm(params["final_norm"], x, cfg.norm_eps)
        logits = Lyr.unembed(params["embed"], x)
        return logits, aux_total

    @jax.named_scope("shared_block")
    def _shared_block(self, p: Tree, own: Tree, x, x0, positions, *, impl,
                      cache=None, cache_pos=None, inv=None):
        """One invocation ``inv`` of a shared block: its output, to be added
        to the next Mamba layer's input, and the invocation's KV cache
        (``cache`` holds every invocation's, written in place at ``inv``)."""
        cfg = self.cfg
        h = jnp.concatenate([x, x0], -1) if cfg.shared_concat else x
        h = Lyr.apply_norm(p["ln1"], h, cfg.norm_eps)
        a, kv = Lyr.attention(p["attn"], h, cfg, positions=positions,
                              cache=cache, cache_pos=cache_pos,
                              cache_layer=None if cache is None else inv,
                              impl=impl)
        h = Lyr.apply_norm(p["ln2"], a, cfg.norm_eps)
        mine = jax.tree.map(
            lambda w: jax.lax.dynamic_index_in_dim(w, inv, 0, False), own)
        m = Lyr.mlp(p["ffn"], h, cfg.mlp_act, adapter=mine.get("adapter"))
        if "linear" in mine:
            m = shard(m @ mine["linear"], "batch", "seq", "embed")
        return m, kv

    def _hybrid_stack(self, params, x, positions, *, impl, cache=None,
                      cache_pos=None, fresh=False):
        """The zamba2 stack: one scan over the Mamba layers.  Each layer's
        xs hold its index, the branch to run before it (0: none, k: shared
        block k-1) and its invocation's index, which picks the adapter,
        the linear and the KV cache of that invocation.  With a cache, the
        stacked Mamba states and KV caches are the scan's carry, each
        layer's written in place; a ``fresh`` (prefill) pass starts each
        Mamba layer from a zero state, whatever the cache holds (attention
        masks the cache's columns past the prompt)."""
        cfg = self.cfg
        L, nb = cfg.num_layers, cfg.shared_blocks
        branch, inv = np.zeros(L, np.int32), np.zeros(L, np.int32)
        for n, i in enumerate(cfg.hybrid_layers):
            branch[i], inv[i] = 1 + n % nb, n
        x0, own = x, params.get("hybrid", {})

        def shared(k):
            def run(x, kv, j):
                return self._shared_block(params["shared"][f"block{k}"],
                                          own, x, x0,
                                          positions, impl=impl, cache=kv,
                                          cache_pos=cache_pos, inv=j)
            return run

        def skip(x, kv, j):
            return jnp.zeros_like(x), kv

        def body(carry, xs):
            (x, st), (p, i, br, j) = carry, xs
            kv = None if st is None else st["shared"]
            extra, kv = jax.lax.switch(
                br, [skip] + [shared(k) for k in range(nb)], x, kv, j)
            if st is None:
                return (Ssm.mamba_block(p, x, cfg, extra=extra)[0], None), None
            mine = None if fresh else jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                st["mamba"])
            y, mine = Ssm.mamba_block(p, x, cfg, state=mine, extra=extra)
            mst = jax.tree.map(
                lambda a, u: jax.lax.dynamic_update_index_in_dim(
                    a, u.astype(a.dtype), i, 0), st["mamba"], mine)
            return (y, {"mamba": mst, "shared": kv}), None

        if cache is None:
            body = _maybe_remat(body, cfg.remat)
        (x, cache), _ = self._scan(
            body, (x, cache),
            (params["blocks"], jnp.arange(L), jnp.asarray(branch),
             jnp.asarray(inv)))
        if cache is not None:
            # handed back as the cache came in (the partitioner may split
            # a replicated leaf otherwise, and the next step then refuses it)
            cache = {**cache, "mamba": {
                k: shard(a, *Ssm.STATE_AXES[k])
                for k, a in cache["mamba"].items()}}
        return x, cache

    def _moe_forward(self, params, x, positions, impl):
        cfg = self.cfg
        period = cfg.moe_layer_period
        n_moe = cfg.num_layers // period

        def group(x, ps):
            aux = jnp.zeros((), jnp.float32)
            if period > 1:
                def inner(x, p):
                    y, _, a = self._dense_block(p, x, positions, impl=impl)
                    return y, a
                x, aux_d = self._scan(
                    _maybe_remat(inner, cfg.remat), x, ps["dense"])
                aux = aux + jnp.sum(aux_d)
            y, _, a = self._dense_block(ps["moe"], x, positions, impl=impl)
            return y, aux + a

        xs: Dict[str, Any] = {"moe": params["moe_blocks"]}
        if period > 1:
            xs["dense"] = _stack_reshape(params["blocks"], n_moe, period - 1)
        x, auxs = self._scan(_maybe_remat(group, cfg.remat)
                               if period == 1 else group, x, xs)
        return x, jnp.sum(auxs)

    def _vlm_forward(self, params, batch, x, positions, impl):
        cfg = self.cfg
        memory = batch["image_embeds"].astype(x.dtype)
        k = cfg.cross_attn_every
        groups = cfg.num_layers // k
        stacked = _stack_reshape(params["blocks"], groups, k)

        def group(x, ps):
            def inner(x, p):
                y, _, _ = self._dense_block(p, x, positions, impl=impl)
                return y, None
            x, _ = self._scan(_maybe_remat(inner, cfg.remat), x,
                                ps["self"])
            y, _, _ = self._dense_block(ps["cross"], x, positions, impl=impl,
                                        memory=memory)
            return y, None

        x, _ = self._scan(
            group, x, {"self": stacked, "cross": params["cross_blocks"]})
        return x, jnp.zeros((), jnp.float32)

    def _encode(self, params, frames):
        """Whisper encoder over stub frame embeddings [B, T, D]."""
        cfg = self.cfg
        x = frames
        b, t, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))

        def body(x, p):
            y, _, _ = self._dense_block(p, x, pos, impl=self._impl(t),
                                        causal=False)
            return y, None
        x, _ = self._scan(_maybe_remat(body, cfg.remat),
                            x, params["encoder"])
        return Lyr.apply_norm(params["enc_final_norm"], x, cfg.norm_eps)

    def _audio_forward(self, params, batch, x, positions, impl):
        cfg = self.cfg
        memory = self._encode(params, batch["frames"].astype(x.dtype))

        def body(x, p):
            y, _, _ = self._dense_block(p, x, positions, impl=impl,
                                        memory=memory)
            return y, None
        x, _ = self._scan(_maybe_remat(body, cfg.remat),
                            x, params["blocks"])
        return x, jnp.zeros((), jnp.float32)

    # ------------------------------------------------------------------
    # loss

    def loss(self, params: Tree, batch: Dict[str, jax.Array]) -> jax.Array:
        cfg = self.cfg
        logits, aux = self.forward(params, batch)
        labels = batch["labels"]
        logits = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
        true_logit = jnp.sum(onehot * logits, axis=-1)
        nll = lse - true_logit
        loss = jnp.mean(nll) + cfg.z_loss * jnp.mean(lse * lse)
        if cfg.num_experts:
            loss = loss + cfg.router_aux_coef * aux
        return loss

    # ------------------------------------------------------------------
    # serving: cache defs / prefill / decode

    def cache_defs(self, batch: int, max_seq: int) -> Tree:
        cfg = self.cfg
        L = cfg.num_layers
        fam = cfg.family
        hd, hkv = cfg.resolved_head_dim, cfg.num_kv_heads

        def kv(layers, seq, cross=False):
            # self-attention caches are [L, B, KV, hd, T], T last (see
            # layers.attention); cross K/V keep _cross_kv's [.., T, hd]
            if cross:
                ax = ("layers", "cache_batch", "cache_heads", "cache_seq",
                      "cache_hd")
                shape = (layers, batch, hkv, seq, hd)
            else:
                ax = ("layers", "cache_batch", "cache_heads", "cache_hd",
                      "cache_seq")
                shape = (layers, batch, hkv, hd, seq)
            return {"k": ParamDef(shape, ax, init="zeros"),
                    "v": ParamDef(shape, ax, init="zeros")}

        if fam == "ssm":
            return Ssm.rwkv_state_defs(cfg, batch, L)
        if fam == "hybrid":
            return {"mamba": Ssm.mamba_state_defs(cfg, batch, L),
                    "shared": kv(len(cfg.hybrid_layers), max_seq)}
        if fam == "audio":
            return {"self": kv(L, max_seq),
                    "cross": kv(L, self.frames_len(max_seq, decode=True),
                                cross=True)}
        if fam == "vlm":
            n_cross = L // cfg.cross_attn_every
            return {"self": kv(L, max_seq),
                    "cross": kv(n_cross, cfg.num_image_tokens,
                                cross=True)}
        return {"self": kv(L, max_seq)}

    def init_cache(self, batch: int, max_seq: int) -> Tree:
        return init_params(jax.random.PRNGKey(0),
                           self.cache_defs(batch, max_seq))

    def cache_shapes(self, batch: int, max_seq: int) -> Tree:
        return param_shapes(self.cache_defs(batch, max_seq))

    def cache_logical_axes(self, batch: int, max_seq: int) -> Tree:
        return param_logical_axes(self.cache_defs(batch, max_seq))

    def frames_len(self, seq: int, decode: bool = False) -> int:
        """Whisper stub-encoder frame count (fixed 1500-frame memory)."""
        return 1500

    # ------------------------------------------------------------------

    def prefill(self, params: Tree, batch: Dict[str, jax.Array],
                cache: Tree) -> Tuple[jax.Array, Tree]:
        """Run the full prompt, filling cache; returns (last logits, cache)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        x = Lyr.embed(params["embed"], tokens)
        impl = self._impl(s)
        x, cache = self._stack_with_cache(params, batch, x, positions, cache,
                                          cache_pos=0, impl=impl, fresh=True)
        with jax.named_scope("head"):
            x = Lyr.apply_norm(params["final_norm"], x[:, -1:], cfg.norm_eps)
            logits = Lyr.unembed(params["embed"], x)
        return logits[:, 0], cache

    def decode_step(self, params: Tree, batch: Dict[str, jax.Array],
                    cache: Tree, pos: jax.Array
                    ) -> Tuple[jax.Array, Tree]:
        """One token step.  batch["tokens"]: [B, 1]; pos: scalar frontier."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b = tokens.shape[0]
        positions = jnp.broadcast_to(pos[None, None], (b, 1)).astype(jnp.int32)
        x = Lyr.embed(params["embed"], tokens)
        x, cache = self._stack_with_cache(params, batch, x, positions, cache,
                                          cache_pos=pos, impl="einsum")
        with jax.named_scope("head"):
            x = Lyr.apply_norm(params["final_norm"], x, cfg.norm_eps)
            logits = Lyr.unembed(params["embed"], x)
        return logits[:, 0], cache

    # ------------------------------------------------------------------

    @jax.named_scope("layers")
    def _stack_with_cache(self, params, batch, x, positions, cache,
                          cache_pos, impl, fresh=False):
        """The layer loop over the cache: the scans with the cache's
        stacking and reshaping around them, all under the ``layers``
        scope.  Self-attention caches are [L, B, KV, hd, T] (T last: see
        ``layers.attention``).  The dense and moe stacks carry the whole
        cache through the scan, so no layer's cache is sliced out, copied
        or re-stacked; the other families pass each layer's cache as a
        slice of the scan's xs and its update as a slice of its ys."""
        cfg = self.cfg
        fam = cfg.family

        if fam == "ssm":
            # rwkv state flows through scan xs/ys (prefill runs the chunked
            # form with t tokens; decode runs the exact single-step form)
            def body2(x, pst):
                p, st = pst
                y, st2 = Ssm.rwkv_block(p, x, cfg, state=st)
                return y, st2
            x, new_state = self._scan(body2, x, (params["blocks"], cache))
            return x, new_state

        if fam == "hybrid":
            return self._hybrid_stack(params, x, positions, impl=impl,
                                      cache=cache, cache_pos=cache_pos,
                                      fresh=fresh)

        if fam == "vlm":
            kk = cfg.cross_attn_every
            groups = cfg.num_layers // kk
            stacked = _stack_reshape(params["blocks"], groups, kk)
            scache = _stack_reshape(cache["self"], groups, kk)
            xkv = cache["cross"]
            if "image_embeds" in batch:    # prefill: compute cross K/V now
                mem = batch["image_embeds"].astype(x.dtype)
                xkv = jax.vmap(
                    lambda p: self._cross_kv(p, mem))(
                        params["cross_blocks"]["xattn"])

            def group(x, xs):
                gp, gc, cp, ckv = xs

                def inner(x, pc):
                    p, c = pc
                    y, c2, _ = self._dense_block(p, x, positions, impl=impl,
                                                 cache=c, cache_pos=cache_pos)
                    return y, c2
                x, c2 = self._scan(inner, x, (gp, gc))
                y, _, _ = self._dense_block(cp, x, positions, impl=impl,
                                            xmemory_kv=ckv)
                return y, (c2, ckv)

            x, (sc2, xkv2) = self._scan(
                group, x, (stacked, scache, params["cross_blocks"], xkv))
            new_self = jax.tree.map(
                lambda a: a.reshape((groups * kk,) + a.shape[2:]), sc2)
            return x, {"self": new_self, "cross": xkv2}

        if fam == "audio":
            xkv = cache["cross"]
            if "frames" in batch:          # prefill: encode + cross K/V
                mem = self._encode(params, batch["frames"].astype(x.dtype))
                xkv = jax.vmap(
                    lambda p: self._cross_kv(p, mem))(
                        params["blocks"]["xattn"])

            def body(x, xs):
                p, c, ckv = xs
                h = Lyr.apply_norm(p["ln1"], x, cfg.norm_eps)
                a, c2 = Lyr.attention(p["attn"], h, cfg, positions=positions,
                                      cache=c, cache_pos=cache_pos, impl=impl)
                x = x + a
                h = Lyr.apply_norm(p["ln_x"], x, cfg.norm_eps)
                x = x + self._cross_from_kv(p["xattn"], h, ckv)
                h = Lyr.apply_norm(p["ln2"], x, cfg.norm_eps)
                x = x + Lyr.mlp(p["ffn"], h)
                return x, (c2, ckv)

            x, (c2, xkv2) = self._scan(
                body, x, (params["blocks"], cache["self"], xkv))
            return x, {"self": c2, "cross": xkv2}

        # dense / moe: the stacked cache is the scans' carry and the layer
        # index rides in their xs, so each layer writes its tokens in place
        # at that index and reads its cache where it lies
        def block(carry, xs):
            (x, c), (p, layer) = carry, xs
            y, c, _ = self._dense_block(p, x, positions, impl=impl, cache=c,
                                        cache_pos=cache_pos,
                                        cache_layer=layer)
            return (y, c), None

        carry = (x, cache["self"])
        if fam == "moe":
            period = cfg.moe_layer_period
            n_moe = cfg.num_layers // period

            def group(carry, xs):
                ps, g = xs
                first = g * period
                if period > 1:
                    carry, _ = self._scan(
                        block, carry,
                        (ps["dense"], first + jnp.arange(period - 1)))
                return block(carry, (ps["moe"], first + period - 1))

            xs: Dict[str, Any] = {"moe": params["moe_blocks"]}
            if period > 1:
                xs["dense"] = _stack_reshape(
                    params["blocks"], n_moe, period - 1)
            carry, _ = self._scan(group, carry, (xs, jnp.arange(n_moe)))
        else:
            carry, _ = self._scan(
                block, carry, (params["blocks"], jnp.arange(cfg.num_layers)))
        x, c = carry
        return x, {"self": c}
