"""The program's spans (``repro.runtime.spans``) and model scopes, read
back from a CPU profiler trace and from the compiled step's metadata."""

import collections
import glob
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import ALL_APPS, CascadeCompiler, CompileCache, PassConfig
from repro.core import passes
from repro.core.passes import CompileContext, PassPipeline
from repro.runtime.spans import span

#: harris with a short anneal: the trace stays a few MB
CFG = PassConfig.full(pnr_backend="jax", sta_backend="numpy", place_moves=2)


def _trace(tmp_path, fn):
    """Run ``fn`` under a CPU profiler trace; its result and the
    ``cascade.`` spans as ``name -> [(seconds, attrs)]`` in start order."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    evs = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs += [(ev.start_ns, ev.name, ev.duration_ns * 1e-9,
                     dict(ev.stats)) for ev in line.events
                    if ev.name.startswith("cascade.")]
    spans = collections.defaultdict(list)
    for _, name, secs, attrs in sorted(evs, key=lambda e: e[0]):
        spans[name].append((secs, attrs))
    return out, spans


def _compile():
    return CascadeCompiler(cache=CompileCache(),
                           stage_cache=CompileCache()).compile(
        ALL_APPS["harris"], CFG, verify=True, use_cache=False)


@pytest.fixture(scope="module")
def traced_harris(tmp_path_factory):
    _compile()                          # jit compiles stay out of the trace
    return _trace(tmp_path_factory.mktemp("trace"), _compile)


def test_span_nests_carries_attributes_and_reports_seconds(tmp_path):
    def nested():
        with span("cascade.outer", n=3) as outer:
            with span("cascade.inner") as inner:
                jnp.ones(4).block_until_ready()
        return outer, inner

    (outer, inner), spans = _trace(tmp_path, nested)
    assert 0 < inner.seconds <= outer.seconds
    (secs, attrs), = spans["cascade.outer"]
    assert attrs == {"n": 3}
    assert spans["cascade.inner"][0][0] <= secs


def test_span_without_jax_only_reads_the_clock():
    """A span never imports jax into a process that has not."""
    code = ("import sys\nfrom repro.runtime.spans import span\n"
            "with span('cascade.x', a=1) as s:\n    pass\n"
            "assert s.seconds >= 0 and 'jax' not in sys.modules\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    subprocess.run([sys.executable, "-c", code], check=True, env=env)


def test_compile_trace_holds_a_span_per_pass(traced_harris):
    res, spans = traced_harris
    executed = res.pass_stats["pipeline"]
    for name in executed:
        assert len(spans[f"cascade.pass.{name}"]) == 1, name
    assert not {k for k in spans if k.startswith("cascade.pass.")} - {
        f"cascade.pass.{n}" for n in executed}
    for name in ("cascade.place.setup", "cascade.place.anneal",
                 "cascade.route.iter", "cascade.route.kernel",
                 "cascade.post_pnr.round", "cascade.sta"):
        assert spans[name], name
    setup, anneal = spans["cascade.place.setup"], spans["cascade.place.anneal"]
    assert len(setup) == len(anneal) == 1
    assert setup[0][1]["nodes"] == res.pass_stats["pnr"]["nodes"]
    assert setup[0][1]["replicas"] == res.pass_stats["pnr"]["place"][
        "replicas"]
    assert setup[0][0] + anneal[0][0] <= spans["cascade.pass.place"][0][0]
    rounds = spans["cascade.post_pnr.round"]
    assert [a["round"] for _, a in rounds] == list(range(len(rounds)))
    assert res.pass_stats["post_pnr"]["iterations"] <= len(rounds)


def test_pass_times_are_the_spans_seconds(traced_harris, monkeypatch):
    res, spans = traced_harris
    for name, secs in res.pass_stats["pass_times"].items():
        (traced, _), = spans[f"cascade.pass.{name}"]
        assert secs <= traced + 1e-4, name
        assert traced - secs < 0.01 + 0.02 * traced, name
    # exactly: the pipeline stores each pass span's own seconds
    seen = {}

    class recording(span):
        __slots__ = ()

        def __exit__(self, *exc):
            super().__exit__(*exc)
            seen[self.name] = self.seconds

    monkeypatch.setattr(passes, "span", recording)
    res = _compile()
    assert {f"cascade.pass.{k}": v for k, v in
            res.pass_stats["pass_times"].items()} == seen


def test_route_counters_agree_with_the_spans(traced_harris):
    res, spans = traced_harris
    route = res.pass_stats["route"]
    assert route["iterations"] == len(spans["cascade.route.iter"]) >= 1
    assert route["kernel_calls"] == len(spans["cascade.route.kernel"]) >= 1
    assert route["shapes"] == sorted(
        {(a["D"], a["S"]) for _, a in spans["cascade.route.kernel"]})
    assert [a["iter"] for _, a in spans["cascade.route.iter"]] == list(
        range(route["iterations"]))


def _jax_arrays(obj, seen=None, path="ctx"):
    """Paths of the ``jax.Array`` objects reachable from ``obj``."""
    seen = set() if seen is None else seen
    if id(obj) in seen or isinstance(obj, (str, bytes, int, float, bool,
                                           np.ndarray, type(None))):
        return []
    seen.add(id(obj))
    if isinstance(obj, jax.Array):
        return [path]
    if isinstance(obj, dict):
        items = [(f"{path}[{k!r}]", v) for k, v in obj.items()]
    elif isinstance(obj, (list, tuple, set, frozenset)):
        items = [(f"{path}[{i}]", v) for i, v in enumerate(obj)]
    elif hasattr(obj, "__dict__") and not callable(obj):
        items = [(f"{path}.{k}", v) for k, v in vars(obj).items()]
    else:
        return []
    return [p for sub, v in items for p in _jax_arrays(v, seen, sub)]


def test_no_device_array_outlives_place_and_route():
    """The pass timers need no device sync only because the jax placer and
    router hand the host numpy results: after ``place`` and ``route`` no
    artifact of the context holds a ``jax.Array``."""
    comp = CascadeCompiler(cache=CompileCache())
    ctx = CompileContext(app=ALL_APPS["harris"], config=CFG,
                         fabric=comp.fabric, timing=comp.timing,
                         energy=comp.energy)
    pipe = PassPipeline.from_config(CFG)
    pipe.run(ctx, until=pipe.names.index("route") + 1)
    assert ctx.executed[-2:] == ["place", "route"]
    assert ctx.design is not None
    assert _jax_arrays(ctx) == []


def test_lm_step_names_its_scopes():
    from repro.configs import get_config
    from repro.models import LM
    cfg = get_config("granite-moe-1b-a400m").smoke()
    model = LM(cfg)
    b, s = 2, 16
    tok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    text = jax.jit(model.decode_step).lower(
        model.shapes(), {"tokens": tok}, model.cache_shapes(b, s),
        pos).compile().as_text()
    names = set()
    for op in text.split('op_name="')[1:]:
        names.update(op.split('"', 1)[0].split("/"))
    assert {"layers", "attention", "kv_update", "moe", "head"} <= names
