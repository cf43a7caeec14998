"""Serving driver: batched prefill + greedy decode loop with a KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-moe-1b-a400m \
        --batch 4 --prompt-len 128 --gen 32 [--smoke]

The mesh spans every visible device (``make_mesh_for``: tensor parallel
over up to 16 of them on the "model" axis, data parallel over the rest),
so the same command serves on one chip, a four-chip host or a pod slice.
``--smoke`` swaps in the family's reduced config (CPU tests).
"""

from __future__ import annotations

import argparse
import time
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, ShapeSpec, get_config
from repro.configs.base import ModelConfig
from repro.distributed import sharding as shd
from repro.launch import steps as S
from repro.launch.jax_cache import use_compile_cache
from repro.launch.mesh import make_mesh_for
from repro.models import LM


def serve(cfg: ModelConfig, mesh, *, batch: int, prompt_len: int, gen: int,
          seed: int = 0) -> Dict[str, object]:
    """Serve ``batch`` random prompts of ``prompt_len`` tokens and decode
    ``gen`` tokens each, greedily, on ``mesh``.

    Weights are random (from ``seed``) and placed with the serve
    shardings.  Returns the generated ids ``[batch, gen]``, the logits of
    every step ``[gen, batch, vocab_size]`` (float32), the compiled prefill and
    decode steps, each parameter's sharding by tree path, and the compile
    and run seconds of prefill and decode.
    """
    model = LM(cfg)
    shd.set_rules(S.rules_for(cfg))
    max_seq = prompt_len + gen
    shape = ShapeSpec("serve", max_seq, batch, "decode")
    out: Dict[str, object] = {}
    with jax.sharding.set_mesh(mesh):
        p_sh, _, c_sh = S.serve_shardings(model, mesh, shape)
        params = jax.jit(model.init, out_shardings=p_sh)(
            jax.random.PRNGKey(seed))
        cache = jax.jit(lambda: model.init_cache(batch, max_seq),
                        out_shardings=c_sh)()
        prompts = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                     (batch, prompt_len), 0, cfg.vocab_size)
        inputs = {"tokens": prompts}
        if cfg.family == "vlm":
            inputs["image_embeds"] = 0.1 * jnp.ones(
                (batch, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
        if cfg.family == "audio":
            inputs["frames"] = 0.1 * jnp.ones((batch, 1500, cfg.d_model),
                                              jnp.bfloat16)
        step = {"tokens": jnp.zeros((batch, 1), jnp.int32)}

        t0 = time.perf_counter()
        prefill = jax.jit(S.make_prefill_step(model), donate_argnums=(2,)
                          ).lower(params, inputs, cache).compile()
        decode = jax.jit(S.make_decode_step(model), donate_argnums=(2,)
                         ).lower(params, step, cache, jnp.int32(0)).compile()
        out["compile_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        logits, cache = prefill(params, inputs, cache)
        logits.block_until_ready()
        out["prefill_s"] = time.perf_counter() - t0

        # the padded vocabulary's extra columns are not tokens
        real = lambda lg: lg[:, :cfg.vocab_size].astype(jnp.float32)
        toks = jnp.argmax(real(logits), -1).astype(jnp.int32)[:, None]
        ids, steps = [toks], [real(logits)]
        t0 = time.perf_counter()
        for i in range(gen - 1):
            logits, cache = decode(params, {"tokens": toks}, cache,
                                   jnp.int32(prompt_len + i))
            toks = jnp.argmax(real(logits), -1).astype(jnp.int32)[:, None]
            ids.append(toks)
            steps.append(real(logits))
        jax.block_until_ready(ids[-1])
        out["decode_s"] = time.perf_counter() - t0
    out["ids"] = jnp.concatenate(ids, axis=1)
    out["logits"] = jnp.stack(steps)
    out["prefill"], out["decode"] = prefill, decode
    out["param_shardings"] = {
        jax.tree_util.keystr(k): v.sharding
        for k, v in jax.tree_util.tree_leaves_with_path(params)}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS),
                    default="granite-moe-1b-a400m")
    ap.add_argument("--smoke", action="store_true",
                    help="the family's reduced config")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    mesh = make_mesh_for()
    b, plen, gen = args.batch, args.prompt_len, args.gen
    r = serve(cfg, mesh, batch=b, prompt_len=plen, gen=gen)

    gen_toks = b * (gen - 1)
    print(f"[serve] {cfg.name} on {dict(mesh.shape)}: compile "
          f"{r['compile_s']:.3f}s")
    print(f"[serve] prefill {b}x{plen} in {r['prefill_s']:.3f}s "
          f"({b * plen / max(r['prefill_s'], 1e-9):.0f} tok/s)")
    print(f"[serve] decode {gen_toks} tokens in {r['decode_s']:.3f}s "
          f"({gen_toks / max(r['decode_s'], 1e-9):.1f} tok/s)")
    print(f"[serve] sample generated ids: {r['ids'][0][:16].tolist()}")
    return r["ids"]


if __name__ == "__main__":
    main()
