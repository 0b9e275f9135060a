"""The closed-loop cells rehearsed end to end on the CPU at a small size:
build, warm-up, window, drain and the reference replay; and the check
that decides ``correct`` refuses the faults a cell can have."""
import pytest

import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from cells import FAULTS, run  # noqa: E402


@pytest.mark.parametrize("cell", ["wiki-backlog", "reddit-mixed-backlog"])
def test_backlog_cell_is_correct(cell):
    rec = run(cell)
    assert rec["correct"], rec["checks"]
    assert rec["compiles_in_window"] == 0
    assert rec["window_rounds"] > 0
    assert rec["attempted"] == rec["events"] > 0
    assert rec["failed"] == 0
    # float32 on both sides here: far inside the chip's limits
    for errs in rec["errors"].values():
        assert errs["memory_err"] < 1e-5
        assert errs["emb_err"] < 1e-5
        assert errs["rows"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_step_is_not_correct(fault):
    rec = run("wiki-backlog", fault=FAULTS[fault])
    assert not rec["correct"], rec["checks"]
