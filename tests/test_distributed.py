"""Distribution substrate: sharding rules, checkpoint/restart, elastic
reshard, fault-tolerant loop, straggler policy, data determinism."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import (CheckpointManager, latest_step,
                              restore_checkpoint, save_checkpoint)
from repro.configs import SHAPES, get_config
from repro.data.pipeline import SyntheticLMData, batch_specs
from repro.distributed import sharding as shd
from repro.launch import steps as S
from repro.launch.mesh import make_smoke_mesh
from repro.models import LM
from repro.runtime import FailureInjector, FaultTolerantLoop, StragglerPolicy
from repro.runtime.fault_tolerance import InjectedFailure


# ---------------------------------------------------------------------------
# sharding rules


def test_resolve_spec_divisibility_fallback():
    mesh = make_smoke_mesh()
    with jax.sharding.set_mesh(mesh):
        # "model" axis size 1 always divides; 17 % 1 == 0 -> kept
        spec = shd.resolve_spec(("embed", "vocab"), dims=(17, 32))
        assert isinstance(spec, P)


def test_resolve_spec_drops_missing_axes():
    mesh = make_smoke_mesh()     # no "pod" axis
    with jax.sharding.set_mesh(mesh):
        spec = shd.resolve_spec(("batch", "seq"), dims=(8, 16))
        flat = []
        for entry in spec:
            if isinstance(entry, tuple):
                flat += list(entry)
            elif entry:
                flat.append(entry)
        assert "pod" not in flat


def test_resolve_spec_never_reuses_axis():
    mesh = make_smoke_mesh()
    rules = shd.rules_with(embed="model", mlp="model")
    with jax.sharding.set_mesh(mesh):
        spec = shd.resolve_spec(("embed", "mlp"), rules=rules, dims=(16, 16))
        used = [a for a in jax.tree.leaves(tuple(spec)) if a]
        assert len(used) == len(set(used))


def test_rules_context():
    shd.set_rules(shd.BASE_RULES)
    with shd.use_rules(shd.SP_RULES):
        assert shd.get_rules()["seq"] == "model"
    assert shd.get_rules()["seq"] is None


# ---------------------------------------------------------------------------
# end-to-end jit train step on the (1,1) smoke mesh with real shardings


@pytest.mark.slow            # jit of a full train step: seconds on 2 vCPUs
def test_train_step_on_smoke_mesh():
    from repro.optim.adamw import AdamWConfig
    cfg = get_config("llama3-8b").smoke()
    model = LM(cfg)
    opt_cfg = AdamWConfig(lr=3e-3, weight_decay=0.0)
    mesh = make_smoke_mesh()
    shape = SHAPES["train_4k"]
    shd.set_rules(S.rules_for(cfg))
    with jax.sharding.set_mesh(mesh):
        st_sh, b_sh = S.train_shardings(model, opt_cfg, mesh, shape)
        step = jax.jit(S.make_train_step(model, opt_cfg),
                       in_shardings=(st_sh, b_sh),
                       out_shardings=(st_sh, NamedSharding(mesh, P())))
        state = S.init_train_state(model, opt_cfg, jax.random.PRNGKey(0))
        data = SyntheticLMData(cfg, SHAPES["train_4k"])
        batch = jax.tree.map(lambda x: x[:2, :16], data.batch(0))
        losses = []
        for i in range(3):
            state, loss = step(state, batch)
            losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]          # same batch 3x must descend


# ---------------------------------------------------------------------------
# checkpointing


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16),
                  "d": jnp.zeros((), jnp.int32)}}
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 7, tree)
    assert latest_step(d) == 7
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)
    out = restore_checkpoint(d, 7, like)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_atomicity(tmp_path):
    """Partial writes never surface: only renamed step dirs are visible."""
    d = str(tmp_path / "ckpt")
    os.makedirs(os.path.join(d, "step_00000003.tmp-abc"))  # crashed save
    assert latest_step(d) is None
    save_checkpoint(d, 4, {"x": jnp.ones(3)})
    assert latest_step(d) == 4


def test_checkpoint_manager_gc_and_async(tmp_path):
    d = str(tmp_path / "ckpt")
    mgr = CheckpointManager(d, keep=2, async_save=True)
    for s in (10, 20, 30):
        mgr.save(s, {"x": jnp.full((2,), s, jnp.float32)})
    mgr.wait()
    steps = sorted(int(p.split("_")[1]) for p in os.listdir(d))
    assert steps == [20, 30]
    step, tree = mgr.restore_latest({"x": jax.ShapeDtypeStruct((2,),
                                                               jnp.float32)})
    assert step == 30 and float(tree["x"][0]) == 30.0


def test_checkpoint_elastic_reshard(tmp_path):
    """Restore onto a different mesh (shardings arg) — elastic rescale."""
    d = str(tmp_path / "ckpt")
    x = jnp.arange(16, dtype=jnp.float32)
    save_checkpoint(d, 1, {"x": x})
    mesh = make_smoke_mesh()
    sh = {"x": NamedSharding(mesh, P("data"))}
    out = restore_checkpoint(d, 1, {"x": jax.ShapeDtypeStruct((16,),
                                                              jnp.float32)},
                             shardings=sh)
    np.testing.assert_array_equal(np.asarray(out["x"]), np.asarray(x))
    assert out["x"].sharding == sh["x"]


# ---------------------------------------------------------------------------
# fault tolerance


def test_fault_tolerant_loop_recovers(tmp_path):
    """Loop hits two injected failures, restores from checkpoint, and ends
    with the same state a failure-free run produces (data is (seed, step)-
    deterministic)."""
    d = str(tmp_path / "ckpt")

    def run(inject):
        store = {}

        def step_fn(state, batch):
            return state + batch

        def save(step, state):
            store[step] = state
            save_checkpoint(d, step, {"s": jnp.float32(state)})

        def restore():
            s = latest_step(d)
            if s is None:
                return None, None
            t = restore_checkpoint(
                d, s, {"s": jax.ShapeDtypeStruct((), jnp.float32)})
            return s, float(t["s"])

        loop = FaultTolerantLoop(
            step_fn=step_fn,
            batch_fn=lambda step: float(step),    # deterministic "data"
            ckpt_save=save, ckpt_restore=restore,
            checkpoint_every=5,
            injector=FailureInjector(fail_at=inject),
        )
        state, step, history = loop.run(0.0, 0, 20)
        return state, history

    clean, _ = run({})
    faulty, hist = run({7: "preemption", 13: "ici-link-down"})
    assert faulty == clean
    assert any(h.startswith("failure@7") for h in hist)
    assert any(h.startswith("restored@") for h in hist)


def test_fault_loop_gives_up_after_max_restarts(tmp_path):
    loop = FaultTolerantLoop(
        step_fn=lambda s, b: s, batch_fn=lambda s: 0,
        ckpt_save=lambda *a: None, ckpt_restore=lambda: (None, None),
        max_restarts=2,
        injector=FailureInjector(fail_at={0: "x", 1: "y", 2: "z", 3: "w"}),
    )
    # injector refires at restart because step resets to 0 each time and
    # steps 0..3 all fail -> exceeds max_restarts
    loop.injector.fail_at = {i: "x" for i in range(50)}
    loop.injector.fired = []
    with pytest.raises(InjectedFailure):
        loop.run(0, 0, 10)


def test_straggler_policy():
    p = StragglerPolicy(deadline_factor=2.0, max_strikes=2)
    for _ in range(8):
        assert not p.observe(1.0)
    assert p.observe(5.0)          # straggler
    assert not p.cordoned
    assert p.observe(6.0)
    assert p.cordoned              # two strikes -> cordon


# ---------------------------------------------------------------------------
# data pipeline determinism


def test_data_pipeline_determinism_and_sharding():
    cfg = get_config("llama3-8b").smoke()
    data = SyntheticLMData(cfg, SHAPES["train_4k"], seed=5)
    b1 = data.batch(3)
    b2 = data.batch(3)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    b3 = data.batch(4)
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))
    # host slicing partitions the global batch exactly
    parts = [data.host_batch(3, h, 4)["tokens"] for h in range(4)]
    np.testing.assert_array_equal(np.concatenate(parts, 0),
                                  np.asarray(b1["tokens"]))


def test_batch_specs_cover_all_cells():
    for arch in ("llama3-8b", "llama-3.2-vision-11b", "whisper-small"):
        cfg = get_config(arch)
        for shape in SHAPES.values():
            specs = batch_specs(cfg, shape)
            assert "tokens" in specs
            if shape.kind == "train":
                assert "labels" in specs
