"""Serve a small model with batched requests: prefill + KV-cache decode.

    PYTHONPATH=src python examples/serve_lm.py [--arch rwkv6-7b]

Runs reduced-family configs of three architectures (dense GQA, attention-
free RWKV6, hybrid Mamba2) through the identical serving path the dry-run
lowers at 32k/500k scale, and reports prefill/decode throughput.
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.launch import steps as S
from repro.launch.mesh import make_smoke_mesh
from repro.models import LM


def serve_one(arch: str, b=4, plen=32, gen=16):
    cfg = get_config(arch).smoke()
    model = LM(cfg)
    shd.set_rules(S.rules_for(cfg))
    mesh = make_smoke_mesh()
    with jax.sharding.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        cache = model.init_cache(b, plen + gen)
        prefill = jax.jit(S.make_prefill_step(model))
        decode = jax.jit(S.make_decode_step(model), donate_argnums=(2,))
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (b, plen), 0, cfg.vocab_size)}
        if cfg.family == "vlm":
            batch["image_embeds"] = 0.1 * jnp.ones(
                (b, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
        if cfg.family == "audio":
            batch["frames"] = 0.1 * jnp.ones((b, 1500, cfg.d_model),
                                             jnp.bfloat16)
        logits, cache = prefill(params, batch, cache)
        toks = jnp.argmax(logits, -1)[:, None]
        t0 = time.time()
        for i in range(gen - 1):
            logits, cache = decode(params, {"tokens": toks}, cache,
                                   jnp.int32(plen + i))
            toks = jnp.argmax(logits, -1)[:, None]
        jax.block_until_ready(toks)
        dt = time.time() - t0
    print(f"[serve_lm] {arch:12s} ({cfg.family:6s}): "
          f"{b * (gen - 1) / dt:7.1f} tok/s decode "
          f"(batch={b}, ctx={plen + gen})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    args = ap.parse_args()
    archs = [args.arch] if args.arch else \
        ["llama3-8b", "rwkv6-7b", "zamba2-2.7b"]
    for a in archs:
        serve_one(a)
    print("[serve_lm] OK")


if __name__ == "__main__":
    main()
