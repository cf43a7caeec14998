"""The plain float32 reference against the program's serve path at the
configuration's smoke() size on the CPU: prefill, then decode through the
cache, on the benchmark's weights."""

import numpy as np
import pytest

from bench.tests import util
from bench.lib import granite_ref, harness as H
from bench.lib.serve_loop import model_config


def _dims():
    dims = H.load_json(H.os.path.join(
        H.BENCH, "configs", "granite-moe-1b-a400m.json"))
    dims.update(util.TINY_LM)
    return dims


@pytest.mark.parametrize("use_flash", [False, True])
def test_program_matches_reference_in_float32(use_flash):
    """With the weights and the cache in float32 the program's logits are
    the reference's to float32 rounding, through the prompt's capacity
    drops (16 tokens x top-2 over 4 experts: 10 slots each) and 6 decode
    steps."""
    import jax
    import jax.numpy as jnp
    from repro.models import LM
    dims = {**_dims(), "use_flash": use_flash}
    cfg = model_config(dims)
    model = LM(cfg)
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     granite_ref.make_weights(dims, 11))
    b, p, g = 2, 16, 6
    cache = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                         model.cache_shapes(b, p + g))
    prompts = np.random.default_rng(0).integers(0, cfg.vocab_size, (b, p))
    logits, cache = jax.jit(model.prefill)(w, {"tokens": jnp.asarray(prompts)},
                                           cache)
    got = [np.asarray(logits[:, :cfg.vocab_size])]
    toks = jnp.argmax(logits[:, :cfg.vocab_size], -1)
    served = [np.asarray(toks)]
    step = jax.jit(model.decode_step)
    for i in range(g - 1):
        logits, cache = step(w, {"tokens": toks[:, None]}, cache,
                             jnp.int32(p + i))
        got.append(np.asarray(logits[:, :cfg.vocab_size]))
        toks = jnp.argmax(logits[:, :cfg.vocab_size], -1)
        served.append(np.asarray(toks))
    got, served = np.stack(got, 1), np.stack(served, 1)       # [B, G, V]
    for r in range(b):
        seq = np.concatenate([prompts[r], served[r, :-1]])
        ref = np.asarray(granite_ref.logits(w, dims, seq, p))[p - 1:]
        np.testing.assert_allclose(got[r], ref, rtol=2e-4, atol=2e-4)


def test_capacity_drops_change_the_result():
    """The reference's capacity rule bites at this size: with a capacity
    factor large enough to drop nothing the prompt's logits differ."""
    import jax
    import jax.numpy as jnp
    dims = _dims()
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     granite_ref.make_weights(dims, 11))
    seq = np.random.default_rng(0).integers(0, dims["vocab_size"], 16)
    a = np.asarray(granite_ref.logits(w, dims, seq, 16))
    b = np.asarray(granite_ref.logits(w, {**dims, "capacity_factor": 4.0},
                                      seq, 16))
    assert np.abs(a - b).max() > 1e-3


def test_served_tokens_and_control():
    """The harness's bf16 serve path at test size: the served tokens sit
    at or next to the reference's best logit, closer on the mean than the
    tokens the fp8 control puts first."""
    cell = util.tiny_cell("granite.decode", seconds=0.5)
    line, out = util.run_line(cell)
    assert line["correct"]
    r = [r for r in out["requests"] if r["complete"]][0]
    w = granite_ref.make_weights(cell.config, cell.seed)
    g = granite_ref.served_gaps(w, cell.config, r["prompts"][0],
                                r["tokens"][0], control=True)
    assert g["gaps"].mean() < g["control_gaps"].mean()
