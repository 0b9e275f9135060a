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
