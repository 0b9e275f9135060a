"""The check's control: the plain reference with its matrix products at
``high`` precision (three bfloat16 passes, the step below the ``highest``
that the configurations state) put in the program's place, on the same
rounds. At this size, where the program runs in float32 on the CPU, the
control reads far above it on every tier, and the cell's own limits find
it not correct while they pass the program, on three seeds."""
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(__file__))
import cells  # noqa: E402
import harness  # noqa: E402

SEEDS = [3, 2 ** 31 + 5, 2 ** 32 + 7]


def _run(cell, seed):
    spec = harness.load_spec()
    _, cfg, _ = harness.resolve(spec, cell)
    rec = harness.run_cell(spec, cell, seed, 0.3, False, time.monotonic(),
                           lambda _m: None, cfg=cells.tiny(cfg), control=True)
    return cfg, rec


@pytest.mark.parametrize("seed", SEEDS)
def test_bfloat16_control_reads_far_above_the_program(seed):
    cfg, rec = _run("reddit-mixed-backlog", seed)
    assert set(rec["control_errors"]) == {lane["name"]
                                          for lane in cfg["lanes"]}
    for lane, ctl in rec["control_errors"].items():
        got = rec["errors"][lane]
        for what in ("memory_err", "memory_rms", "emb_err", "emb_rms"):
            assert ctl[what] > 1e-6 > 2 * got[what], (lane, what)


@pytest.mark.parametrize("cell", ["wiki-backlog", "reddit-mixed-backlog"])
@pytest.mark.parametrize("seed", SEEDS)
def test_the_control_in_the_programs_place_is_not_correct(cell, seed):
    _, rec = _run(cell, seed)
    assert rec["correct"], rec["checks"]
    assert not rec["control_correct"], rec["control_checks"]
    assert set(rec["control_checks"]) == set(rec["checks"])
