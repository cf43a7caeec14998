"""Production training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
        --shape train_4k --steps 100 [--smoke] [--ckpt-dir /path] \
        [--fail-at 30,60] [--resume]

The mesh spans every visible device (``make_mesh_for``), so the same
command trains on one chip, a four-chip host or a pod slice;
``--multi-pod`` builds the 512-chip (pod, data, model) production mesh.
``--smoke`` shrinks the model to its reduced family config so the identical
control flow (mesh -> shardings -> jit -> fault-tolerant loop ->
checkpoints) is exercised on CPU.
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs import ARCHS, SHAPES, get_config
from repro.data.pipeline import SyntheticLMData
from repro.distributed import sharding as shd
from repro.launch import steps as S
from repro.launch.jax_cache import use_compile_cache
from repro.launch.mesh import make_mesh_for, make_production_mesh
from repro.models import LM
from repro.runtime import FailureInjector, FaultTolerantLoop, StragglerPolicy


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="llama3-8b")
    ap.add_argument("--shape", choices=sorted(SHAPES), default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true",
                    help="the family's reduced config (CPU)")
    ap.add_argument("--batch", type=int, default=0,
                    help="override global batch (smoke default 4)")
    ap.add_argument("--seq", type=int, default=0,
                    help="override sequence length (smoke default 128)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", default="",
                    help="comma-separated steps at which to inject failures")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    shape = SHAPES[args.shape]
    mesh = (make_production_mesh(multi_pod=True) if args.multi_pod
            else make_mesh_for())
    if args.smoke:
        cfg = cfg.smoke()
        shape = shape.__class__(shape.name, args.seq or 128,
                                args.batch or 4, shape.kind)
    elif args.batch or args.seq:
        shape = shape.__class__(shape.name, args.seq or shape.seq_len,
                                args.batch or shape.global_batch, shape.kind)

    model = LM(cfg)
    opt_cfg = S.make_optimizer_config(cfg, total_steps=args.steps)
    shd.set_rules(S.rules_for(cfg))
    data = SyntheticLMData(cfg, shape)

    with jax.sharding.set_mesh(mesh):
        st_sh, b_sh = S.train_shardings(model, opt_cfg, mesh, shape)
        step_fn = jax.jit(S.make_train_step(model, opt_cfg),
                          in_shardings=(st_sh, b_sh),
                          out_shardings=(st_sh, NamedSharding(mesh, P())),
                          donate_argnums=(0,))
        state = S.init_train_state(model, opt_cfg, jax.random.PRNGKey(0))

        mgr = None
        start = 0
        if args.ckpt_dir:
            mgr = CheckpointManager(args.ckpt_dir, keep=3)
            if args.resume:
                st, restored = mgr.restore_latest(state)
                if restored is not None:
                    start, state = st, restored
                    print(f"[train] resumed from step {start}")

        losses = {}

        def wrapped_step(st, batch):
            st2, loss = step_fn(st, batch)
            losses[len(losses)] = float(loss)
            return st2

        injector = FailureInjector(fail_at={
            int(s): "injected" for s in args.fail_at.split(",") if s})
        loop = FaultTolerantLoop(
            step_fn=wrapped_step,
            batch_fn=lambda s: data.batch(s),
            ckpt_save=(lambda s, st: mgr.save(s, st)) if mgr else
            (lambda s, st: None),
            ckpt_restore=(lambda: mgr.restore_latest(state)) if mgr else
            (lambda: (None, None)),
            checkpoint_every=args.ckpt_every,
            injector=injector,
            straggler=StragglerPolicy(),
        )
        t0 = time.time()
        state, end_step, history = loop.run(state, start, args.steps)
        dt = time.time() - t0

    ls = list(losses.values())
    print(f"[train] {args.arch} {cfg.name}: {len(ls)} steps in {dt:.1f}s "
          f"({dt / max(1, len(ls)):.2f}s/step)")
    if ls:
        k = max(1, len(ls) // 10)
        print(f"[train] loss {ls[0]:.4f} -> {sum(ls[-k:]) / k:.4f} "
              f"(first -> mean of last {k})")
    if history:
        print(f"[train] events: {history}")
    if mgr:
        mgr.wait()
    return ls


if __name__ == "__main__":
    main()
