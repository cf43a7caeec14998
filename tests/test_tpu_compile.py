"""Compile the main path's device programs for one TPU v5e chip, without a
chip: the TPU compiler refuses what interpret mode and the CPU backend
accept (misaligned Pallas blocks, too much VMEM, unsupported ops).

Every program is captured at the shapes a real ``harris`` x4 compile (and
a granite-moe-1b-a400m serve) produces here on the CPU, then lowered and
compiled for a described ``v5e:2x2`` chip.  Nothing runs on the TPU.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ALL_APPS, CascadeCompiler, CompileCache, PassConfig
from repro.core import place_jax, route_jax, sim_vec, sta_vec
from repro.core.sim import simulate


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries written for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def harris_programs():
    """(jitted fn, call args) of each jax kernel a harris x4 compile and
    simulation run, recorded from a CPU run at the same shapes."""
    captured = {}

    def recording(name, factory):
        def make(*static):
            fn = factory(*static)

            def call(*args):
                captured.setdefault(name, (fn, args))
                return fn(*args)
            return call
        return make

    mp = pytest.MonkeyPatch()
    for mod, attr, name in ((place_jax, "_jitted_anneal", "place"),
                            (route_jax, "_jitted_router", "route"),
                            (sta_vec, "_jitted_propagate", "sta"),
                            (sim_vec, "_jitted_dense", "sim")):
        mp.setattr(mod, attr, recording(name, getattr(mod, attr)))
    try:
        res = CascadeCompiler(cache=CompileCache(),
                              stage_cache=CompileCache()).compile(
            ALL_APPS["harris"],
            PassConfig.full(pnr_backend="jax", sta_backend="jax"))
        g = res.design.netlist.to_dfg()
        ins = {n: [1] * 16 for n, nd in g.nodes.items() if nd.kind == "input"}
        simulate(g, ins, 16_384, backend="jax")
    finally:
        mp.undo()
    return captured


def _compile_for(chip, fn, args):
    sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), args)
    return fn.lower(*sds).compile()


@pytest.mark.parametrize("kernel", ["place", "route", "sim"])
def test_harris_kernel_compiles_for_v5e(one_chip, harris_programs, kernel):
    fn, args = harris_programs[kernel]
    assert _compile_for(one_chip, fn, args) is not None


def test_harris_sta_compiles_for_v5e(one_chip, harris_programs):
    fn, args = harris_programs["sta"]
    with jax.enable_x64(True):           # the propagation carries float64
        compiled = _compile_for(one_chip, fn, args)
    assert compiled is not None


def test_flash_attention_compiles_for_v5e_at_granite_widths(one_chip):
    from repro.kernels.flash_attention import flash_attention
    q = jax.ShapeDtypeStruct((1, 16, 2048, 64), jnp.bfloat16,
                             sharding=one_chip)
    compiled = flash_attention.lower(q, q, q, causal=True,
                                     interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_decode_compiles_for_v5e_at_granite_widths(one_chip):
    from repro.kernels.flash_decode import flash_decode
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q = sds((4, 8, 2, 64), jnp.bfloat16)
    cache = sds((4, 8, 64, 2048), jnp.bfloat16)
    lengths = sds((4,), jnp.int32)
    compiled = flash_decode.lower(q, cache, cache, lengths,
                                  interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _granite_decode_step(chip, monkeypatch, *, layers, b, s, donate=False):
    """The serve path's decode step at granite's widths, compiled for
    ``chip`` with the Mosaic flash_decode kernel (as on the chip, not the
    CPU's interpreter)."""
    import importlib
    from repro.configs import get_config
    from repro.models import LM
    fd = importlib.import_module("repro.kernels.flash_decode.flash_decode")
    monkeypatch.setattr(fd, "resolve_interpret", lambda i: False)
    fd.flash_decode.clear_cache()
    try:
        cfg = get_config("granite-moe-1b-a400m").replace(num_layers=layers,
                                                         use_flash=True)
        model = LM(cfg)
        on_chip = lambda tree: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=chip), tree)
        step = jax.jit(model.decode_step,
                       donate_argnums=(2,) if donate else ())
        return step.lower(
            on_chip(model.shapes()),
            {"tokens": on_chip(jax.ShapeDtypeStruct((b, 1), jnp.int32))},
            on_chip(model.cache_shapes(b, s)),
            on_chip(jax.ShapeDtypeStruct((), jnp.int32))).compile()
    finally:
        fd.flash_decode.clear_cache()


def test_granite_decode_step_keeps_flash_decode_kernel_name(one_chip,
                                                            monkeypatch):
    """The serve path's decode step, at granite's widths, holds the Pallas
    kernel as a custom call whose name (the op's name in a TPU trace)
    starts with ``flash_decode``: the benchmark's kernel time reads it."""
    text = _granite_decode_step(one_chip, monkeypatch, layers=2, b=4,
                                s=1024).as_text()
    kernels = [line.split("=", 1)[0].strip() for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels and all(k.startswith("%flash_decode") for k in kernels)


def test_granite_prefill_decode_shape_compiles_for_v5e(one_chip,
                                                       monkeypatch):
    """A decode step of granite.prefill's shortest bucket (B 16, a
    1056-long cache: the plan's last block is partial) compiles with the
    Mosaic kernel, one ``%flash_decode`` custom call in the layer scan."""
    text = _granite_decode_step(one_chip, monkeypatch, layers=2, b=16,
                                s=1056).as_text()
    kernels = [line.split("=", 1)[0].strip() for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1 and kernels[0].startswith("%flash_decode")


#: ops that may hold a cache-sized value without moving the cache
_CACHE_SIZED_OK = {"parameter", "bitcast", "get-tuple-element", "tuple",
                   "while", "dynamic-update-slice"}


def test_granite_decode_step_moves_no_cache(one_chip, monkeypatch):
    """At the benchmark's decode shape (B 48, T 1536, the cache donated)
    the step reads and writes the stacked KV cache in place: no op but a
    parameter, bitcast, tuple, loop or the token's dynamic-update-slice
    holds a value the size of the cache or of any number of its layers (no
    copy, slice, pad or fresh buffer), nothing cache-sized is temporary,
    and both cache leaves alias the step's outputs."""
    import re
    layers, b, s, kv, hd = 4, 48, 1536, 8, 64
    compiled = _granite_decode_step(one_chip, monkeypatch, layers=layers,
                                    b=b, s=s, donate=True)
    text = compiled.as_text()
    layer_elems = b * kv * hd * s
    op = re.compile(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(")
    moved, entry, cache_params = [], False, set()
    for line in text.splitlines():
        entry = line.startswith("ENTRY") or (entry and line != "}")
        m = op.match(line)
        if not m:
            continue
        n = 1
        for d in filter(None, m.group(1).split(",")):
            n *= int(d)
        if n % layer_elems or not 1 <= n // layer_elems <= layers:
            continue
        if m.group(2) not in _CACHE_SIZED_OK:
            moved.append(line.strip()[:160])
        if entry and m.group(2) == "parameter" and n == layers * layer_elems:
            cache_params.add(int(re.search(r"parameter\((\d+)\)",
                                           line).group(1)))
    assert not moved, "\n".join(moved)
    assert len(cache_params) == 2
    header = text.splitlines()[0]
    aliased = {int(p) for p in re.findall(r"\((\d+), \{\}, may-alias\)",
                                          header)}
    assert cache_params <= aliased, header[:300]
    assert compiled.memory_analysis().temp_size_in_bytes < layer_elems * 2


def test_flash_decode_compiles_for_v5e_at_zamba2_widths(one_chip):
    """One chip's share of zamba2-7b's decode: 8 KV heads of 224, one
    query head each, a 2048-long stacked cache."""
    from repro.kernels.flash_decode import flash_decode
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q = sds((32, 8, 1, 224), jnp.bfloat16)
    cache = sds((13, 32, 8, 224, 2048), jnp.bfloat16)
    compiled = flash_decode.lower(q, cache, cache, sds((32,), jnp.int32),
                                  sds((), jnp.int32),
                                  interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def four_chips(one_chip):
    """The described v5e:2x2 host as the serve path's (data, model) mesh."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import AxisType, Mesh
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def test_zamba2_decode_step_on_four_chips(four_chips, monkeypatch):
    """zamba2-7b's decode step at its widths (12 layers: both shared
    blocks), 4-way tensor parallel on a described v5e:2x2 host, batch 32
    over a 2048-long cache donated: flash_decode runs as Mosaic kernels
    under shard_map, the stacked KV caches and Mamba states alias the
    outputs with nothing of cache size copied, and the step all-reduces."""
    import importlib
    import re
    from repro.configs import ShapeSpec, get_config
    from repro.distributed import sharding as shd
    from repro.launch import steps as S
    from repro.models import LM
    fd = importlib.import_module("repro.kernels.flash_decode.flash_decode")
    monkeypatch.setattr(fd, "resolve_interpret", lambda i: False)
    fd.flash_decode.clear_cache()
    b, t = 32, 2048
    cfg = get_config("zamba2-7b").replace(num_layers=12, use_flash=True)
    model = LM(cfg)
    mesh = four_chips
    prev = shd.get_rules()
    shd.set_rules(S.rules_for(cfg))
    try:
        with jax.set_mesh(mesh):
            p_sh, _, c_sh = S.serve_shardings(
                model, mesh, ShapeSpec("serve", t, b, "decode"))
            on = lambda tree, sh: jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=s), tree, sh)
            rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
            compiled = jax.jit(model.decode_step, donate_argnums=(2,)).lower(
                on(model.shapes(), p_sh),
                {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32,
                                                sharding=rep)},
                on(model.cache_shapes(b, t), c_sh),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)).compile()
    finally:
        shd.set_rules(prev)
        fd.flash_decode.clear_cache()
    text = compiled.as_text()
    kernels = {line.split("=", 1)[0].strip() for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line}
    assert kernels and all(k.startswith("%flash_decode") for k in kernels)
    assert re.search(r"all-reduce(-start)?\(", text)
    header = text.splitlines()[0]
    assert len(re.findall(r"may-alias", header)) == 5     # k, v, 3 states
    # per chip, the smallest stacked leaf: the f32 states 12 x 32 x 28 x
    # 4096 (a copy of any cache leaf would pass it)
    ssm = 12 * b * 28 * 4096 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < ssm
    assert not [line for line in text.splitlines()
                if re.search(r"= f32\[12,32,28,4096\]\S* copy\(", line)]
