"""The open-loop path rehearsed end to end on the CPU at a small size (the
Wikipedia configuration under the ``poisson_online`` mix, which no cell
serves yet): a load-generator child process sends a seeded Poisson
schedule over the JSON-lines transport, the waiter stamps each round, and
every event due is accounted for, applied in order, and checked against
the reference."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from cells import FAULTS, run  # noqa: E402


def test_online_cell_is_correct():
    rec = run("wiki-backlog", seconds=1.0, traffic="poisson_online")
    assert rec["correct"], rec["checks"]
    assert rec["compiles_in_window"] == 0
    assert rec["attempted"] > 0
    assert rec["events"] + rec["failed"] == rec["attempted"]
    assert 0 < rec["p50_latency_ms"] <= rec["p99_latency_ms"]
    assert 0 < rec["valid_rows"] <= rec["dispatched_rows"]
    for errs in rec["errors"].values():
        assert errs["memory_err"] < 1e-5


def test_online_altered_answer_is_not_correct():
    rec = run("wiki-backlog", seconds=1.0, traffic="poisson_online",
              fault=FAULTS["altered_answer"])
    assert not rec["correct"]
