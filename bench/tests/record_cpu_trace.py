"""Record the small CPU trace that test_trace.py reduces.

    JAX_PLATFORMS=cpu python bench/tests/record_cpu_trace.py

Three jitted matrix products inside ``bench.step`` spans, with host sleeps
between them, all inside the ``bench.window`` span; the trace is copied
to ``bench/tests/data/cpu_trace.xplane.pb``.
"""

import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def main():
    from bench.lib.trace import newest_xplane
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    shutil.copy(newest_xplane(tmp), os.path.join(HERE, "data",
                                                 "cpu_trace.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
    main()
