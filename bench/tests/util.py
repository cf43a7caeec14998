"""Cells at test sizes, driven through the harness without its look for
a chip (the CPU backend, Pallas kernels in interpret mode)."""

from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.lib import harness as H  # noqa: E402

#: granite's configuration at the program's smoke() size
TINY_LM = {"num_hidden_layers": 4, "hidden_size": 64,
           "num_attention_heads": 4, "num_key_value_heads": 4,
           "head_dim": 16, "intermediate_size": 128, "vocab_size": 512,
           "num_local_experts": 4, "num_experts_per_tok": 2}


def tiny_cell(name: str, seed: int = 7, seconds: float = 1.0,
              trace: bool = False, fault=None, config=None,
              traffic=None, tmp=None) -> H.Cell:
    bench = H.benchmark()
    cell = H.cell_from_benchmark(bench, name, seed=seed, seconds=seconds,
                                 trace=trace, t_process=time.perf_counter())
    if cell.traffic["loop"] == "serve_loop":
        cell.config.update(TINY_LM)
        # 4 requests x 32 tokens: a single near-tie is 1/128 of the mean
        cell.traffic.update(shapes=[[4, 16, 32]], order=[0], check_requests=4,
                            check_tokens=128, trace_after_s=0.0,
                            trace_seconds=0.5)
        if len(H.load_json(os.path.join(
                H.BENCH, "traffic", bench_traffic(bench, name)))
               ["shapes"]) > 1:
            cell.traffic.update(shapes=[[4, 16, 32], [2, 24, 32]],
                                order=[0, 1])
    else:
        cell.traffic.update(warmup_designs=1, check_designs=2,
                            trace_after_s=0.0, trace_seconds=0.5)
    cell.config.update(config or {})
    cell.traffic.update(traffic or {})
    cell.fault = fault
    if tmp is not None:
        cell.out_dir = str(tmp)
    from bench.lib.meter import CompileMeter
    cell.meter = CompileMeter()
    return cell


def bench_traffic(bench: dict, name: str) -> str:
    return H.find(bench["workloads"], name, "workload")["traffic"] + ".json"


def run_line(cell: H.Cell) -> dict:
    out = H.loop_module(cell).run(cell)
    return H.result_line(H.benchmark(), cell, out), out
