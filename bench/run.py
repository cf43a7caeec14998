"""Run one benchmark cell on the chips of this machine and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  One process:
it sets up (imports, weights or apps, warm-up and compiles: ``setup_s``),
measures for ``--seconds``, checks what the timed path produced against
the benchmark's own reference, and prints the result as the last line of
standard output.  ``--trace 1`` takes a profiler trace of part of the
window and prints the cell's per-layer metrics instead of its end-to-end
ones.  Without a TPU, or with fewer chips than the cell asks for, it
exits 1 and prints no result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.lib import harness as H
    bench = H.benchmark()
    cell = H.cell_from_benchmark(bench, args.workload, seed=args.seed,
                                 seconds=args.seconds, trace=bool(args.trace),
                                 t_process=T_PROCESS)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); jax "
              f"sees {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1

    from repro.launch.jax_cache import use_compile_cache
    use_compile_cache()
    # every program goes to the persistent cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cell.t_ready = H.now()

    from bench.lib.meter import CompileMeter
    cell.meter = CompileMeter()
    out = H.loop_module(cell).run(cell)
    H.print_result(H.result_line(bench, cell, out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
