"""The controls at test size, through bench/control.py's readings: the
program's sound runs pass every number, and the control fails one.
Compile cells: a pipelining register dropped from each dense design, an
op altered in each sparse one.  Serve cells: the reference in fp8 put in
the program's place, judged on the same requests."""

import pytest

from bench import control
from bench.tests import util


@pytest.mark.parametrize("cell", ["harris.compile", "granite.block_compile"])
def test_compile_control_fails(cell, tmp_path):
    c = util.tiny_cell(cell, seed=5, seconds=0.5, tmp=tmp_path,
                       traffic={"pnr_backend": "numpy"})
    r = control.reading(c, [None])
    assert r["output_mismatches"] == 0 and r["illegal"] == 0
    assert r["cp_gap_ns"] == 0.0
    assert r["control_output_mismatches"] > 0


@pytest.mark.parametrize("cell", ["granite.decode", "granite.prefill"])
def test_serve_control_fails(cell, tmp_path):
    """At the smoke() size the program's numbers keep within the cell's
    limits, and the fp8 control's pass one of them."""
    c = util.tiny_cell(cell, seed=5, seconds=0.5, tmp=tmp_path)
    r = control.reading(c, [None])
    limits = c.traffic["limits"]
    assert all(r[k] <= lim for k, lim in limits.items())
    assert any(r["control_" + k] > lim for k, lim in limits.items())
