"""Readings that set a cell's limits: the program's sound runs and the
control's, on many seeds, in one process.  Not part of a benchmark run.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

Serve cells: per seed, new weights under the same compiled serve path, a
short window at the cell's own load, then the gap statistics of the
served tokens (``serve_loop.gap_stats``: widest and mean gap, share off
the reference's argmax) and, on the same requests, of the tokens the fp8
control puts first (``control_*``).
Compile cells: per seed, a short window whose designs are broken the way
the control breaks them (a pipelining register dropped on a dense
design, an op altered on a sparse one), then the cell's checks.
One JSON line per seed; exits 1 without a TPU.  The tests call
``reading`` at test sizes on the CPU.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    from bench.lib import harness as H
    from bench.lib.meter import CompileMeter
    bench = H.benchmark()
    meter = CompileMeter()
    box = [None]
    for seed in args.seeds:
        cell = H.cell_from_benchmark(bench, args.workload, seed=seed,
                                     seconds=args.seconds, trace=False,
                                     t_process=time.perf_counter())
        cell.meter = meter
        print(json.dumps(reading(cell, box)), flush=True)
    return 0


def reading(cell, box) -> dict:
    """One seed's readings; ``box[0]`` carries a built serve path from one
    seed to the next."""
    from bench.lib.harness import loop_module
    mod = loop_module(cell)
    if cell.traffic["loop"] == "serve_loop":
        if box[0] is None:
            box[0] = mod.Server(cell)
        out = mod.run(cell, server=box[0], control=True)
        return {"seed": cell.seed, **{k: v for k, (v, _) in
                                      out["checks"].items()}}
    sound = mod.run(cell)["checks"]
    cell.fault = "control"
    broken = mod.run(cell)["checks"]
    return {"seed": cell.seed,
            **{k: v for k, (v, _) in sound.items()},
            **{"control_" + k: v for k, (v, _) in broken.items()}}


if __name__ == "__main__":
    sys.exit(main())
