"""End-to-end behaviour tests: the paper's full flow + the framework's
train/serve paths, wired the way a user drives them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.apps import ALL_APPS
from repro.core.compiler import CascadeCompiler, PassConfig

pytestmark = pytest.mark.slow        # full-flow integration: seconds each


def test_paper_headline_end_to_end():
    """Compile one dense app unpipelined vs full flow and check the
    paper's headline bands (abstract: dense CP 7-34x, EDP 7-190x)."""
    c = CascadeCompiler()
    app = ALL_APPS["gaussian"]
    r0 = c.compile(app, PassConfig.unpipelined(place_moves=60))
    r1 = c.compile(app, PassConfig.full(place_moves=60), verify=True)
    cp = r0.sta.critical_path_ns / r1.sta.critical_path_ns
    edp = r0.power.edp_js / r1.power.edp_js
    assert r1.pass_stats["verified"] is True
    assert 5.0 < cp < 40.0, cp
    assert 5.0 < edp < 200.0, edp


def test_lm_lowering_bridge_runs_cascade():
    """An assigned arch's block tile lowers to a CGRA DFG and benefits from
    the full pipelining flow."""
    from repro.configs import get_config
    from repro.core.lmmap import lower_block
    c = CascadeCompiler()
    spec = lower_block(get_config("llama3-8b"))
    r0 = c.compile(spec, PassConfig.unpipelined(place_moves=50))
    r1 = c.compile(spec, PassConfig.full(place_moves=50))
    assert r0.sta.critical_path_ns / r1.sta.critical_path_ns > 3.0


def test_train_loop_with_failure_recovers_and_descends(tmp_path):
    """The full training stack: jit step + checkpoints + injected failure;
    loss must descend end to end."""
    from repro.checkpoint import CheckpointManager
    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.data.pipeline import SyntheticLMData
    from repro.distributed import sharding as shd
    from repro.launch import steps as S
    from repro.launch.mesh import make_smoke_mesh
    from repro.models import LM
    from repro.optim.adamw import AdamWConfig
    from repro.runtime import FailureInjector, FaultTolerantLoop
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = get_config("llama3-8b").smoke()
    shape = ShapeSpec("t", 32, 2, "train")
    model = LM(cfg)
    opt_cfg = AdamWConfig(lr=3e-3, weight_decay=0.0)
    shd.set_rules(S.rules_for(cfg))
    mesh = make_smoke_mesh()
    data = SyntheticLMData(cfg, shape)
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=2, async_save=False)
    losses = []
    with jax.sharding.set_mesh(mesh):
        st_sh, b_sh = S.train_shardings(model, opt_cfg, mesh, shape)
        step = jax.jit(S.make_train_step(model, opt_cfg),
                       in_shardings=(st_sh, b_sh),
                       out_shardings=(st_sh, NamedSharding(mesh, P())))
        state = S.init_train_state(model, opt_cfg, jax.random.PRNGKey(0))

        def wrapped(st, batch):
            st2, loss = step(st, batch)
            losses.append(float(loss))
            return st2

        loop = FaultTolerantLoop(
            step_fn=wrapped, batch_fn=lambda i: data.batch(i),
            ckpt_save=lambda i, st: mgr.save(i, st),
            ckpt_restore=lambda: mgr.restore_latest(state),
            checkpoint_every=5,
            injector=FailureInjector(fail_at={8: "preempt"}))
        state, end, hist = loop.run(state, 0, 16)
    assert end == 16
    assert any(h.startswith("restored@5") for h in hist)
    assert np.mean(losses[-3:]) < losses[0]


def test_serve_path_generates():
    """Prefill + decode loop produces deterministic greedy tokens."""
    from repro.configs import get_config
    from repro.distributed import sharding as shd
    from repro.launch import steps as S
    from repro.launch.mesh import make_smoke_mesh
    from repro.models import LM

    cfg = get_config("llama3-8b").smoke()
    model = LM(cfg)
    shd.set_rules(S.rules_for(cfg))
    with jax.sharding.set_mesh(make_smoke_mesh()):
        params = model.init(jax.random.PRNGKey(0))
        cache = model.init_cache(2, 24)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                  cfg.vocab_size)
        logits, cache = model.prefill(params, {"tokens": toks}, cache)
        out = []
        nxt = jnp.argmax(logits, -1)[:, None]
        for i in range(6):
            logits, cache = model.decode_step(
                params, {"tokens": nxt}, cache, jnp.int32(16 + i))
            nxt = jnp.argmax(logits, -1)[:, None]
            out.append(nxt)
        ids = jnp.concatenate(out, 1)
    assert ids.shape == (2, 6)
    assert bool(jnp.all((ids >= 0) & (ids < cfg.padded_vocab)))


def test_sparse_full_flow_preserves_token_streams():
    """Sparse (ready-valid) full flow: FIFO-pipelined, placed-and-routed
    design replays the source app's token streams exactly."""
    from repro.core.dfg import INPUT
    from repro.core.sim import simulate_sparse

    c = CascadeCompiler()
    app = ALL_APPS["elemmul"]
    full = c.compile(app, PassConfig.full(place_moves=50))
    g_ref = app.build(1)
    rng = np.random.default_rng(4)
    ins = {n: rng.integers(0, 99, size=12).tolist()
           for n, nd in g_ref.nodes.items() if nd.kind == INPUT}
    assert simulate_sparse(g_ref, ins) == \
        simulate_sparse(full.design.netlist.to_dfg(), ins)


def test_pipeline_partitioner_beats_naive_on_heterogeneous_stack():
    """Cascade's post-PnR loop, applied to pipeline stages, balances
    heterogeneous stacks by cost: strictly better than equal-layer split on
    zamba2 (mamba layers + heavy shared-attention layers), never worse on
    the homogeneous-ish llama4 interleave."""
    from repro.configs import ARCHS, SHAPES
    from repro.distributed.pipeline import plan_for
    z = plan_for(ARCHS["zamba2-2.7b"], SHAPES["train_4k"],
                 num_stages=4, chips_per_stage=64, microbatches=8)
    assert z["cascade"].beat_s < z["naive"].beat_s * 0.99
    l4 = plan_for(ARCHS["llama4-maverick-400b-a17b"], SHAPES["train_4k"],
                  num_stages=4, chips_per_stage=64, microbatches=8)
    assert l4["cascade"].beat_s <= l4["naive"].beat_s * 1.001
