"""The GDELT cell (``gdelt-backlog``: 200-d static node features, no edge
features, one population of actors) rehearsed end to end on the CPU at a
small size: every lane is served on the fused tier, Eq. 11's projection
inside the kernel, and the check that decides ``correct`` passes it while
it refuses the faults such a cell can have and the control."""
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(__file__))
import cells  # noqa: E402
import harness  # noqa: E402
import stream  # noqa: E402

CELL = "gdelt-backlog"


def _tiny_cfg():
    spec = harness.load_spec()
    _, cfg, _ = harness.resolve(spec, CELL)
    return cells.tiny(cfg)


def test_every_gdelt_lane_runs_on_the_fused_tier():
    cfg = _tiny_cfg()
    graph = stream.generate(stream.GraphShape(**cfg["graph"]), cells.SEED)
    mgr, _w, tids = harness.build(cfg, cells.SEED, graph, lambda _m: None)
    lanes = mgr.describe()
    assert [v["tier"] for v in lanes.values()] == ["fused"]
    assert sorted(t for v in lanes.values() for t in v["tenants"]) == tids
    # no edge features: one placeholder row, not a padded row per edge
    assert mgr.edge_feats.shape == (1, 1, 128)
    assert mgr.node_feats.shape == (graph.shape.n_nodes, 1, 256)


def test_gdelt_fused_cell_is_correct():
    rec = cells.run(CELL)
    assert rec["correct"], rec["checks"]
    assert rec["compiles_in_window"] == 0
    assert rec["window_rounds"] > 0
    assert set(rec["errors"]) == {"np4-fused"}
    errs = rec["errors"]["np4-fused"]
    assert errs["memory_err"] < 1e-5 and errs["emb_err"] < 1e-5
    assert errs["rows"] > 0
    # every valid event's two rows went through the fused kernel
    assert rec["counts"]["fused_rows"] == 2 * rec["events"] > 0


@pytest.mark.parametrize("fault", sorted(cells.NODE_FEAT_FAULTS))
def test_a_broken_gdelt_fused_step_is_not_correct(fault):
    rec = cells.run(CELL, fault=cells.NODE_FEAT_FAULTS[fault])
    assert not rec["correct"], rec["checks"]


def test_the_control_in_the_gdelt_programs_place_is_not_correct():
    spec = harness.load_spec()
    rec = harness.run_cell(spec, CELL, cells.SEED + 1, 0.3, False,
                           time.monotonic(), lambda _m: None,
                           cfg=_tiny_cfg(), control=True)
    assert rec["correct"], rec["checks"]
    assert not rec["control_correct"], rec["control_checks"]


def test_a_lane_the_fused_kernel_does_not_cover_is_refused():
    # the teacher (vanilla attention, cosine encoder) has no fused
    # kernel: the session serves it on the staged tier, and a lane that
    # names "fused" would measure the cell under a false name
    cfg = _tiny_cfg()
    cfg["lanes"] = [{"name": "teacher-fused", "variant": "vanilla+cosine",
                     "tier": "fused", "params": "teacher", "k": 10,
                     "sampler": "recent", "count": 1}]
    graph = stream.generate(stream.GraphShape(**cfg["graph"]), cells.SEED)
    with pytest.raises(ValueError, match="lane 'teacher-fused' names tier "
                                         "'fused', but the session runs it "
                                         "on tier 'staged'"):
        harness.build(cfg, cells.SEED, graph, lambda _m: None)
