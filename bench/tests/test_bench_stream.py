"""The traffic source: the same seed gives the same inputs, a tenant's
replay never goes back in time across the stream's end, and the open-loop
schedule offers the mix's rate, bursts included."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import stream  # noqa: E402

SHAPE = stream.GraphShape(n_users=60, n_items=40, n_edges=2000)


def test_same_seed_same_graph():
    a, b = stream.generate(SHAPE, 2 ** 31 + 9), stream.generate(SHAPE,
                                                                 2 ** 31 + 9)
    c = stream.generate(SHAPE, 7)
    for x, y in ((a.src, b.src), (a.ts, b.ts), (a.edge_feats, b.edge_feats)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.src, c.src)
    assert a.src.max() < 60 <= a.dst.min() and a.dst.max() < 100


def test_replay_wraps_with_monotone_time():
    g = stream.generate(SHAPE, 1)
    rp = stream.TenantReplay(g, 1500)
    src, dst, eid, ts = rp.take(5000)
    assert eid[0] == 1500 and eid[500] == 0 and eid[2500] == 0
    assert np.all(np.diff(ts) >= 0)
    np.testing.assert_array_equal(src, g.src[eid])
    src2, *_rest, ts2 = rp.take(10)
    assert ts2[0] >= ts[-1]


@pytest.mark.parametrize("burst", [None, {"period_s": 0.5,
                                          "on_share": 0.25}])
def test_schedule_offers_the_rate(burst):
    g = stream.generate(SHAPE, 1)
    traffic = {"rate_eps": 4000, "tenant_zipf_s": 1.0}
    if burst:
        traffic["burst"] = burst
    reps = [stream.TenantReplay(g, s) for s in stream.tenant_starts(g, 4)]
    s = stream.schedule(traffic, reps, 5.0, 2 ** 31 + 3)
    assert abs(len(s.due) - 20000) < 5 * 20000 ** 0.5
    assert np.all(np.diff(s.due) >= 0) and s.due[-1] < 5.0
    share = np.bincount(s.tenant, minlength=4) / len(s.due)
    assert share[0] > share[1] > share[2] > share[3]   # Zipf over tenants
    for t in range(4):
        assert np.all(np.diff(s.ts[s.tenant == t]) >= 0)
    if burst:
        phase = np.mod(s.due, 0.5)
        assert phase.max() < 0.125 + 1e-9
    reps2 = [stream.TenantReplay(g, x) for x in stream.tenant_starts(g, 4)]
    s2 = stream.schedule(traffic, reps2, 5.0, 2 ** 31 + 3)
    np.testing.assert_array_equal(s.due, s2.due)
    np.testing.assert_array_equal(s.eid, s2.eid)


ACTORS = stream.GraphShape(n_users=16682, n_items=0, n_edges=20000,
                           f_edge=0, f_feat=200, bipartite=False)


def test_one_population_draws_both_roles_without_self_loops():
    a = stream.generate(ACTORS, 2 ** 31 + 9)
    b = stream.generate(ACTORS, 2 ** 31 + 9)
    c = stream.generate(ACTORS, 7)
    for x, y in ((a.src, b.src), (a.dst, b.dst), (a.ts, b.ts),
                 (a.node_feats, b.node_feats)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.dst, c.dst)
    assert not np.any(a.src == a.dst)
    assert 0 <= min(a.src.min(), a.dst.min())
    assert max(a.src.max(), a.dst.max()) < ACTORS.n_nodes == 16682
    # one population: the busiest sources are busy destinations too
    top = np.argsort(np.bincount(a.src, minlength=16682))[-20:]
    assert np.all(np.bincount(a.dst, minlength=16682)[top] > 0)
    assert a.edge_feats.shape == (20000, 0)
    assert a.node_feats.shape == (16682, 200)
    assert a.node_feats.dtype == np.float32
    assert np.all(np.isfinite(a.node_feats)) and a.node_feats.std() > 0.1


def test_one_population_batches_hold_a_vertex_in_both_roles():
    # Zipf(1.2) over 16,682 actors: every 200-edge batch of this stream
    # holds vertices that are a source and a destination in it (on average
    # 27 a batch on two seeds), so the chronological last write across the
    # two roles runs every round
    g = stream.generate(ACTORS, 2 ** 31 + 9)
    both = [np.intersect1d(g.src[i:i + 200], g.dst[i:i + 200]).size
            for i in range(0, g.n_edges, 200)]
    assert min(both) > 0
    assert 15 < np.mean(both) < 40


def test_node_features_do_not_move_the_stream():
    # the stream's own draws are the same with and without node features
    base = stream.GraphShape(n_users=60, n_items=40, n_edges=2000)
    with_f = stream.GraphShape(n_users=60, n_items=40, n_edges=2000,
                               f_feat=8)
    a, b = stream.generate(base, 3), stream.generate(with_f, 3)
    assert a.node_feats is None and b.node_feats.shape == (100, 8)
    for x, y in ((a.src, b.src), (a.dst, b.dst), (a.ts, b.ts),
                 (a.edge_feats, b.edge_feats)):
        np.testing.assert_array_equal(x, y)


def test_one_population_has_no_items():
    with pytest.raises(ValueError, match="n_items 0"):
        stream.GraphShape(n_users=60, n_items=40, n_edges=10,
                          bipartite=False)
