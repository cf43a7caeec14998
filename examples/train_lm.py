"""End-to-end LM training driver: a ~100M-parameter llama-family model
trained for a few hundred steps on the deterministic synthetic pipeline,
with checkpointing, an injected mid-run failure, and automatic recovery.

    PYTHONPATH=src python examples/train_lm.py            # ~100M, 300 steps
    PYTHONPATH=src python examples/train_lm.py --tiny     # CI-speed smoke

The same launcher (repro.launch.train) runs the full assigned configs on
the production mesh; this example pins a container-sized config.
"""

import argparse
import shutil
import tempfile

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.data.pipeline import SyntheticLMData
from repro.distributed import sharding as shd
from repro.launch import steps as S
from repro.launch.mesh import make_smoke_mesh
from repro.models import LM, param_count
from repro.runtime import FailureInjector, FaultTolerantLoop, StragglerPolicy


def config_100m():
    """llama-family ~100M: 12L x 512d x 2048ff, 32k vocab."""
    return get_config("llama3-8b").replace(
        name="llama-100m", num_layers=12, d_model=512, num_heads=8,
        num_kv_heads=4, head_dim=64, d_ff=2048, vocab_size=32000,
        remat="none")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=0)
    args = ap.parse_args()

    if args.tiny:
        cfg = get_config("llama3-8b").smoke()
        steps, b, s = args.steps or 20, 4, 64
    else:
        cfg = config_100m()
        steps, b, s = args.steps or 300, 4, 256
    shape = ShapeSpec("example", s, b, "train")
    model = LM(cfg)
    print(f"[example] {cfg.name}: "
          f"{param_count(model.param_defs()) / 1e6:.1f}M params, "
          f"{steps} steps of {b}x{s} tokens")

    opt_cfg = S.make_optimizer_config(cfg, total_steps=steps)
    shd.set_rules(S.rules_for(cfg))
    mesh = make_smoke_mesh()
    data = SyntheticLMData(cfg, shape)
    ckpt_dir = tempfile.mkdtemp(prefix="train_lm_ckpt_")
    mgr = CheckpointManager(ckpt_dir, keep=2, async_save=True)

    with jax.sharding.set_mesh(mesh):
        st_sh, b_sh = S.train_shardings(model, opt_cfg, mesh, shape)
        step_fn = jax.jit(S.make_train_step(model, opt_cfg),
                          in_shardings=(st_sh, b_sh),
                          out_shardings=(st_sh, NamedSharding(mesh, P())),
                          donate_argnums=(0,))
        state = S.init_train_state(model, opt_cfg, jax.random.PRNGKey(0))

        losses = []

        def wrapped(st, batch):
            st2, loss = step_fn(st, batch)
            losses.append(float(loss))
            return st2

        loop = FaultTolerantLoop(
            step_fn=wrapped,
            batch_fn=lambda i: data.batch(i),
            ckpt_save=lambda i, st: mgr.save(i, st),
            ckpt_restore=lambda: mgr.restore_latest(state),
            checkpoint_every=max(10, steps // 6),
            injector=FailureInjector(fail_at={steps // 2: "sim-preemption"}),
            straggler=StragglerPolicy(),
        )
        state, end, history = loop.run(state, 0, steps)

    k = max(1, len(losses) // 10)
    print(f"[example] loss {losses[0]:.4f} -> "
          f"{sum(losses[-k:]) / k:.4f} over {len(losses)} executed steps")
    print(f"[example] fault-tolerance events: {history}")
    if steps >= 20:       # too few steps to clear warmup otherwise
        assert sum(losses[-k:]) / k < losses[0], "training must reduce loss"
    mgr.wait()
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    print("[example] OK")


if __name__ == "__main__":
    main()
