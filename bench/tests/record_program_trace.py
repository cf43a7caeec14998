"""Record the small CPU trace that test_program_spans.py reads.

    JAX_PLATFORMS=cpu python bench/tests/record_program_trace.py

Four compiles in the span layout of the program's own (``cascade.pass.*``
around the passes, the placer's set-up and anneal, the router's iterations
and kernel calls, the pipelining rounds and their timing runs), written
with the program's span helper: a jitted matrix product stands in for the
device work, host sleeps for the host's.  The first and the last compile
fall outside the ``bench.window`` span.  The trace is copied to
``bench/tests/data/program_trace.xplane.pb``.
"""

import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def compile_like(f, x, iters: int) -> None:
    from repro.runtime.spans import span
    with jax.profiler.TraceAnnotation("bench.compile"):
        with span("cascade.pass.place"):
            with span("cascade.place.setup", replicas=8, nodes=86, K=32):
                time.sleep(0.002)
            with span("cascade.place.anneal", replicas=8, nodes=86, K=32):
                f(x).block_until_ready()
        with span("cascade.pass.route"):
            for it in range(iters):
                with span("cascade.route.iter", iter=it, dirty=4):
                    with span("cascade.route.kernel", T=168, D=8, S=4):
                        f(x).block_until_ready()
                    time.sleep(0.001)
        with span("cascade.pass.post_pnr"):
            for r in range(3):
                with span("cascade.post_pnr.round", round=r, registers=r):
                    with span("cascade.sta"):
                        time.sleep(0.001)
        with span("cascade.pass.verify"):
            time.sleep(0.002)
    time.sleep(0.002)


def main():
    from bench.lib.trace import newest_xplane
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    compile_like(f, x, 2)
    with jax.profiler.TraceAnnotation("bench.window"):
        compile_like(f, x, 1)
        compile_like(f, x, 3)
    compile_like(f, x, 2)
    jax.profiler.stop_trace()
    shutil.copy(newest_xplane(tmp), os.path.join(HERE, "data",
                                                 "program_trace.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    main()
