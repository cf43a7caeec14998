"""The harness, without its look for a chip, drives a run at test size
with the timed path broken underneath, and ``correct`` comes out false:
once for each fault the cell can have.  Serve cells: a decode step that
returns its cache unchanged, and tokens altered where they are produced.
Compile cells: a pipelining register dropped (dense), an op altered, a
node moved onto another's tile, and the critical path misreported."""

import pytest

from bench.tests import util

SERVE = [("granite.decode", "state_unchanged"),
         ("granite.decode", "token_altered"),
         ("granite.prefill", "state_unchanged"),
         ("granite.prefill", "token_altered")]
COMPILE = [("harris.compile", "drop_register"),
           ("harris.compile", "alter_op"),
           ("harris.compile", "misplace"),
           ("harris.compile", "wrong_cp"),
           ("granite.block_compile", "alter_op"),
           ("granite.block_compile", "misplace"),
           ("granite.block_compile", "wrong_cp")]


@pytest.mark.parametrize("cell,fault", SERVE + COMPILE)
def test_fault_is_caught(cell, fault, tmp_path):
    kw = {}
    if "compile" in cell:
        kw["traffic"] = {"pnr_backend": "numpy"}    # the CPU's fast placer
    line, _ = util.run_line(util.tiny_cell(cell, seconds=0.5, fault=fault,
                                           tmp=tmp_path, **kw))
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("cell", ["granite.decode", "granite.prefill",
                                  "harris.compile", "granite.block_compile"])
def test_sound_run_is_correct(cell, tmp_path):
    kw = {"traffic": {"pnr_backend": "numpy"}} if "compile" in cell else {}
    line, _ = util.run_line(util.tiny_cell(cell, seconds=0.5, tmp=tmp_path,
                                           **kw))
    assert line["correct"] is True, line["checks"]
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device", "checks"}
    assert list(line)[-1] == "checks"
