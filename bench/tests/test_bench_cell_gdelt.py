"""A GDELT-shaped configuration (static node features, no edge features,
one population of actors) rehearsed end to end on the CPU at a small
size, through the same harness as the benchmark's cells: the program
serves Eq. 11's ``s + W_s f + b_s`` on its staged and XLA tiers, the
reference checks it, and the faults such a cell can have, the node
features left out among them, are not correct."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
import cells  # noqa: E402


def test_gdelt_shaped_cell_is_correct():
    rec = cells.run("wiki-backlog", cfg=cells.gdelt_shaped())
    assert rec["correct"], rec["checks"]
    assert rec["compiles_in_window"] == 0
    assert rec["window_rounds"] > 0
    assert set(rec["errors"]) == {"np4-staged", "teacher-ref"}
    for errs in rec["errors"].values():
        assert errs["memory_err"] < 1e-5
        assert errs["emb_err"] < 1e-5
        assert errs["rows"] > 0
    assert rec["counts"]["step_flops"] > 0
    assert rec["counts"]["fused_rows"] == 0


@pytest.mark.parametrize("fault", sorted(cells.NODE_FEAT_FAULTS))
def test_a_broken_gdelt_shaped_step_is_not_correct(fault):
    rec = cells.run("wiki-backlog", cfg=cells.gdelt_shaped(),
                    fault=cells.NODE_FEAT_FAULTS[fault])
    assert not rec["correct"], rec["checks"]


def test_the_node_feature_projection_is_load_bearing():
    # with W_s zero the program serves s + b_s: every lane's embeddings
    # leave the reference by far more than the limit allows
    rec = cells.run("wiki-backlog", cfg=cells.gdelt_shaped(),
                    fault=cells.zeroed_w_s)
    for lane in ("np4-staged", "teacher-ref"):
        c = rec["checks"][f"emb_rms {lane}"]
        assert c["value"] > 10 * c["limit"], (lane, c)


def test_a_lane_on_another_tier_than_it_names_is_refused():
    # the fused kernel carries no node features: the session would serve
    # this lane on the staged tier, and the cell be measured under a
    # false name
    import harness
    import stream
    cfg = cells.tiny(cells.gdelt_shaped())
    cfg["lanes"][0].update(name="np4-fused", tier="fused")
    graph = stream.generate(stream.GraphShape(**cfg["graph"]), cells.SEED)
    with pytest.raises(ValueError, match="lane 'np4-fused' names tier "
                                         "'fused', but the session runs it "
                                         "on tier 'staged'"):
        harness.build(cfg, cells.SEED, graph, lambda _m: None)
