"""Sharded tenant fabric (serving/cluster.py) on a forced 8-device host
mesh: trajectories through ShardedSessionManager must be BITWISE-identical
to the unsharded SessionManager, snapshots must restore across mesh shapes
and continue identically, and cohort slots must be released eagerly.

Needs >= 8 devices — run via ``make test-sharded`` (which sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8``); under the plain
tier-1 suite (1 CPU device, no XLA_FLAGS by design — see conftest.py) the
whole module skips.
"""
import os

import jax
import pytest

if jax.device_count() < 8:
    pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                "(make test-sharded)", allow_module_level=True)

import jax.numpy as jnp
import numpy as np

from repro.core import pipeline as pl, tgn
from repro.data import stream as stream_mod
from repro.data import temporal_graph as tgd
from repro.distributed import checkpoint as ckpt
from repro.serving import cluster as cl
from repro.serving.session import SessionManager


@pytest.fixture(scope="module")
def small_graph():
    return tgd.wikipedia_like(n_edges=500)


def _dims(g, f=8):
    return dict(n_nodes=g.cfg.n_nodes, n_edges=g.n_edges, f_edge=172,
                f_mem=f, f_time=f, f_emb=f, m_r=10)


def _setup(g, variant="sat+lut+np4", key=0, f=8):
    cfg = pl.variant_config(variant, **_dims(g, f))
    params = tgn.init_params(jax.random.key(key), cfg)
    return cfg, params, jnp.asarray(g.edge_feats)


def _feeds(g, tids, rounds=3, batch=30):
    return {t: list(stream_mod.fixed_count(
        g, batch, window=slice(50 * i, 50 * i + batch * rounds), seed=i))
        for i, t in enumerate(tids)}


def _assert_state_equal(a, b, msg=""):
    for f in a._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"{msg}:{f}")


# ---------------------------------------------------------------------------
# acceptance: sharded == unsharded, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mesh", ["tenant=8", "tenant=4,vertex=2"])
def test_sharded_bitwise_matches_unsharded(small_graph, mesh):
    """Five tenants (a non-multiple of the tenant axis: mesh padding slots
    stay idle-masked) on a sharded mesh reproduce the unsharded session's
    per-round embeddings AND final states bitwise."""
    g = small_graph
    cfg, params, ef = _setup(g)
    ref = SessionManager(params, ef, model=cfg)
    sh = cl.ShardedSessionManager(params, ef, model=cfg, mesh=mesh)
    rt = [ref.add_tenant() for _ in range(5)]
    st = [sh.add_tenant() for _ in range(5)]
    assert sh.cohort_of(st[0]).capacity == 8
    spec = sh.cohort_of(st[0]).state.memory.sharding.spec
    assert spec[0] == "tenant"
    fr, fs = _feeds(g, rt), _feeds(g, st)
    for r in range(3):
        o1 = ref.step({t: fr[t][r] for t in rt})
        o2 = sh.step({t: fs[t][r] for t in st})
        for t1, t2 in zip(rt, st):
            np.testing.assert_array_equal(
                np.asarray(o1[t1].emb_src), np.asarray(o2[t2].emb_src),
                err_msg=f"round {r} {t2} src")
            np.testing.assert_array_equal(
                np.asarray(o1[t1].emb_dst), np.asarray(o2[t2].emb_dst),
                err_msg=f"round {r} {t2} dst")
    for t1, t2 in zip(rt, st):
        _assert_state_equal(ref.state_of(t1), sh.state_of(t2), msg=t2)


def test_sharded_idle_and_ragged_rounds(small_graph):
    """Idle tenants and ragged per-tenant batch sizes behave identically
    to the unsharded session on the mesh (masking composes with mesh
    padding)."""
    g = small_graph
    cfg, params, ef = _setup(g, key=1)
    ref = SessionManager(params, ef, model=cfg)
    sh = cl.ShardedSessionManager(params, ef, model=cfg, mesh="tenant=8")
    rt = [ref.add_tenant() for _ in range(3)]
    st = [sh.add_tenant() for _ in range(3)]
    small = next(iter(stream_mod.fixed_count(g, 16, window=slice(0, 16))))
    big = next(iter(stream_mod.fixed_count(g, 40, window=slice(80, 120),
                                           seed=7)))
    o1 = ref.step({rt[0]: small, rt[2]: big})   # rt[1] idles; ragged B
    o2 = sh.step({st[0]: small, st[2]: big})
    assert set(o2) == {st[0], st[2]}
    np.testing.assert_array_equal(np.asarray(o1[rt[0]].emb_src),
                                  np.asarray(o2[st[0]].emb_src))
    np.testing.assert_array_equal(np.asarray(o1[rt[2]].emb_src),
                                  np.asarray(o2[st[2]].emb_src))
    for t1, t2 in zip(rt, st):
        _assert_state_equal(ref.state_of(t1), sh.state_of(t2), msg=t2)


def test_mixed_sampler_cohorts_on_mesh(small_graph):
    """Cohorts of different sampler backends each get their own sharded
    stacked tables; one launch per cohort per round, bitwise equal to the
    unsharded fleet."""
    g = small_graph
    cfg, params, ef = _setup(g, key=2)
    variants = ("sat+lut+np4", "sat+lut+np4+uniform",
                "sat+lut+np4+reservoir")
    ref = SessionManager(params, ef, model=cfg)
    sh = cl.ShardedSessionManager(params, ef, model=cfg, mesh="tenant=2")
    rt = [ref.add_tenant(v) for v in variants]
    st = [sh.add_tenant(v) for v in variants]
    fr, fs = _feeds(g, rt, rounds=2), _feeds(g, st, rounds=2)
    for r in range(2):
        ref.step({t: fr[t][r] for t in rt})
        sh.step({t: fs[t][r] for t in st})
    # coalesced (default): the whole 3-cohort round is ONE compiled launch
    assert sh.metrics[-1]["launches"] == 1
    for t1, t2 in zip(rt, st):
        _assert_state_equal(ref.state_of(t1), sh.state_of(t2), msg=t2)


def test_mixed_kernel_tier_fleet_on_mesh(small_graph):
    """A fleet mixing the FUSED single-pass lane with a STAGED lane (same
    variant, two kernel tiers, plus a fused reservoir cohort) on the
    sharded fabric replays bitwise-identically to the unsharded mixed-tier
    session — the fused kernel runs inside the one coalesced mesh launch."""
    g = small_graph
    cfg, params, ef = _setup(g, key=5)
    lanes = ((None, "fused"), (None, "staged"),
             ("sat+lut+np4+reservoir", "fused"))
    ref = SessionManager(params, ef, model=cfg, use_kernels="staged")
    sh = cl.ShardedSessionManager(params, ef, model=cfg,
                                  use_kernels="staged", mesh="tenant=2")
    rt = [ref.add_tenant(v, use_kernels=t) for v, t in lanes]
    st = [sh.add_tenant(v, use_kernels=t) for v, t in lanes]
    assert {c.tier for c in sh._cohorts.values()} == {"fused", "staged"}
    fr, fs = _feeds(g, rt, rounds=3), _feeds(g, st, rounds=3)
    for r in range(3):
        o1 = ref.step({t: fr[t][r] for t in rt})
        o2 = sh.step({t: fs[t][r] for t in st})
        assert sh.metrics[-1]["launches"] == 1
        for t1, t2 in zip(rt, st):
            np.testing.assert_array_equal(
                np.asarray(o1[t1].emb_src), np.asarray(o2[t2].emb_src),
                err_msg=f"round {r} {t2} src")
    for t1, t2 in zip(rt, st):
        _assert_state_equal(ref.state_of(t1), sh.state_of(t2), msg=t2)


# ---------------------------------------------------------------------------
# coalesced cross-cohort rounds on the mesh
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mesh", ["tenant=8", "tenant=4,vertex=2"])
def test_sharded_coalesced_matches_percohort_bitwise(small_graph, mesh):
    """A mixed 3-cohort fleet (8 tenants) on the mesh replays
    BITWISE-identically under the coalesced single-launch round (states
    donated, mesh placements pinned) and the per-cohort sharded baseline,
    through ragged widths and idle tenants — with exactly ONE compiled
    execution per coalesced round."""
    g = small_graph
    cfg, params, ef = _setup(g, key=6)
    variants = ("sat+lut+np4", "sat+lut+np2", "sat+lut+np4+reservoir")
    m1 = cl.ShardedSessionManager(params, ef, model=cfg, mesh=mesh)
    m2 = cl.ShardedSessionManager(params, ef, model=cfg, mesh=mesh,
                                  coalesce=False)
    t1 = [m1.add_tenant(variants[i % 3]) for i in range(8)]
    t2 = [m2.add_tenant(variants[i % 3]) for i in range(8)]
    for r, w in enumerate((30, 18, 30)):
        bs = {}
        for i in range(8):
            if r == 1 and i % 4 == 1:        # some tenants idle round 1
                # (i=1 and i=5 — every cohort keeps at least one active
                # member, so the per-cohort baseline still launches 3x)
                continue
            lo = 40 * i + r * w
            bs[i] = next(iter(stream_mod.fixed_count(
                g, w, window=slice(lo, lo + w), seed=i)))
        before = m1._coalesced.calls if m1._coalesced is not None else 0
        o1 = m1.step({t1[i]: b for i, b in bs.items()})
        o2 = m2.step({t2[i]: b for i, b in bs.items()})
        assert m1._coalesced.calls == before + 1
        assert m1.metrics[-1]["launches"] == 1
        assert m2.metrics[-1]["launches"] == 3
        for i in bs:
            np.testing.assert_array_equal(
                np.asarray(o1[t1[i]].emb_src), np.asarray(o2[t2[i]].emb_src),
                err_msg=f"round {r} tenant {i}")
            # the coalesced round's compiled split places each tenant's
            # outputs where the per-cohort baseline's eager cut does
            for f in ("emb_src", "emb_dst", "attn_logits", "nbr_valid",
                      "nbr_dt"):
                assert (getattr(o1[t1[i]], f).sharding
                        == getattr(o2[t2[i]], f).sharding), (r, i, f)
    for a, b in zip(t1, t2):
        _assert_state_equal(m1.state_of(a), m2.state_of(b), msg=a)
    # the super-batch row space covers every cohort's mesh capacity
    n_tenant_shards = dict(m1.mesh.shape).get("tenant", 1)
    assert m1._coalesced.rows % n_tenant_shards == 0


def test_sharded_coalesced_matches_unsharded_session(small_graph):
    """Coalesced rounds on the mesh reproduce the UNSHARDED coalesced
    session bitwise (the fabric contract composed with the fused round)."""
    g = small_graph
    cfg, params, ef = _setup(g, key=7)
    variants = ("sat+lut+np4", "sat+lut+np4+uniform")
    flat = SessionManager(params, ef, model=cfg)
    sh = cl.ShardedSessionManager(params, ef, model=cfg, mesh="tenant=4")
    ft = [flat.add_tenant(v) for v in variants for _ in range(2)]
    st = [sh.add_tenant(v) for v in variants for _ in range(2)]
    fr, fs = _feeds(g, ft), _feeds(g, st)
    for r in range(3):
        o1 = flat.step({t: fr[t][r] for t in ft})
        o2 = sh.step({t: fs[t][r] for t in st})
        for a, b in zip(ft, st):
            np.testing.assert_array_equal(np.asarray(o1[a].emb_src),
                                          np.asarray(o2[b].emb_src),
                                          err_msg=f"round {r} {b}")
    assert flat.metrics[-1]["launches"] == sh.metrics[-1]["launches"] == 1
    for a, b in zip(ft, st):
        _assert_state_equal(flat.state_of(a), sh.state_of(b), msg=b)


@pytest.mark.parametrize("coalesce", [True, False])
def test_mixed_model_fleet_on_mesh_matches_unsharded(small_graph,
                                                     coalesce):
    """The per-lane parameter dimension on the 8-device mesh: a teacher
    lane + two student weight sets in one sharded session replay
    BITWISE-identically to the unsharded mixed-model session, coalesced
    and per-cohort, with the launch counters pinned — every registered
    set rides the mesh replicated."""
    g = small_graph
    cfg, params, ef = _setup(g, key=20)
    tcfg = pl.variant_config("teacher", **_dims(g))
    tparams = tgn.init_params(jax.random.key(21), tcfg)
    sparams = tgn.init_params(jax.random.key(22), cfg)
    lanes = (("sat+lut+np4", None), ("teacher", "teacher-v1"),
             ("sat+lut+np4", "student-B"))

    def fleet(mk):
        mgr = mk()
        mgr.register_params("teacher-v1", tparams)
        mgr.register_params("student-B", sparams)
        return mgr, [mgr.add_tenant(v, params=p) for v, p in lanes]

    flat, ft = fleet(lambda: SessionManager(
        params, ef, model=cfg, coalesce=coalesce))
    sh, st = fleet(lambda: cl.ShardedSessionManager(
        params, ef, model=cfg, mesh="tenant=2", coalesce=coalesce))
    assert sum(1 for v in sh.describe().values()
               if isinstance(v, dict) and "tenants" in v) == 3
    # registered sets are mesh-replicated (same placement as the default)
    mem = jax.tree.leaves(sh.param_store.get("teacher-v1"))[0]
    assert mem.sharding.mesh.shape == sh.mesh.shape
    fr, fs = _feeds(g, ft), _feeds(g, st)
    for r in range(3):
        o1 = flat.step({t: fr[t][r] for t in ft})
        o2 = sh.step({t: fs[t][r] for t in st})
        assert sh.metrics[-1]["launches"] == (1 if coalesce else 3)
        for a, b in zip(ft, st):
            np.testing.assert_array_equal(np.asarray(o1[a].emb_src),
                                          np.asarray(o2[b].emb_src),
                                          err_msg=f"round {r} {b}")
    if coalesce:
        assert sh._coalesced.traces == 1
        assert sh.summary()["launches_per_round"] == 1
    for a, b in zip(ft, st):
        _assert_state_equal(flat.state_of(a), sh.state_of(b), msg=b)


# ---------------------------------------------------------------------------
# snapshot / restore / migration across mesh shapes
# ---------------------------------------------------------------------------


def test_snapshot_restores_across_mesh_shapes_and_continues(small_graph,
                                                            tmp_path):
    """The elastic acceptance path: snapshot a tenant mid-stream on an
    8-way mesh, restore onto a 2x2 tenant x vertex mesh AND onto the
    unsharded session, and continue all three identically (bitwise)."""
    g = small_graph
    cfg, params, ef = _setup(g, key=3)
    root = str(tmp_path)
    ref = SessionManager(params, ef, model=cfg)
    sh = cl.ShardedSessionManager(params, ef, model=cfg, mesh="tenant=8")
    a_ref, a_sh = ref.add_tenant(), sh.add_tenant()
    feed = list(stream_mod.fixed_count(g, 30, window=slice(0, 150)))
    for b in feed[:3]:                       # mid-stream
        ref.step({a_ref: b})
        sh.step({a_sh: b})
    cl.snapshot_tenant(sh, a_sh, root, step=3)
    assert cl.list_snapshots(root) == {a_sh: 3}
    assert cl.snapshot_meta(root, a_sh)["variant"] == "sat+lut+np4"

    sh2 = cl.ShardedSessionManager(params, ef, model=cfg,
                                   mesh="tenant=2,vertex=2")
    flat = SessionManager(params, ef, model=cfg)
    b_sh = cl.restore_tenant(sh2, root, a_sh)
    b_flat = cl.restore_tenant(flat, root, a_sh, name="revived")
    assert b_flat == "revived"
    _assert_state_equal(sh.state_of(a_sh), sh2.state_of(b_sh), "restored")
    for b in feed[3:]:                       # continue on every topology
        o_ref = ref.step({a_ref: b})[a_ref]
        o_sh2 = sh2.step({b_sh: b})[b_sh]
        o_flat = flat.step({b_flat: b})[b_flat]
        np.testing.assert_array_equal(np.asarray(o_ref.emb_src),
                                      np.asarray(o_sh2.emb_src))
        np.testing.assert_array_equal(np.asarray(o_ref.emb_src),
                                      np.asarray(o_flat.emb_src))
    _assert_state_equal(ref.state_of(a_ref), sh2.state_of(b_sh), "sh2")
    _assert_state_equal(ref.state_of(a_ref), flat.state_of(b_flat), "flat")


def test_migrate_tenant_between_meshes(small_graph, tmp_path):
    """migrate_tenant moves a live tenant to a different mesh shape and
    releases its source slot; the trajectory continues bitwise."""
    g = small_graph
    cfg, params, ef = _setup(g, key=4)
    src = cl.ShardedSessionManager(params, ef, model=cfg, mesh="tenant=8")
    dst = cl.ShardedSessionManager(params, ef, model=cfg, mesh="tenant=4")
    ref = SessionManager(params, ef, model=cfg)
    a_src, a_ref = src.add_tenant(name="hot"), ref.add_tenant()
    feed = list(stream_mod.fixed_count(g, 30, window=slice(0, 120)))
    for b in feed[:2]:
        src.step({a_src: b})
        ref.step({a_ref: b})
    moved = cl.migrate_tenant(src, a_src, dst, str(tmp_path), step=2)
    assert moved == "hot" and src.tenants == ()
    for b in feed[2:]:
        o_ref = ref.step({a_ref: b})[a_ref]
        o_dst = dst.step({moved: b})[moved]
        np.testing.assert_array_equal(np.asarray(o_ref.emb_src),
                                      np.asarray(o_dst.emb_src))
    _assert_state_equal(ref.state_of(a_ref), dst.state_of(moved), "moved")
    # migrating back under the same root auto-continues the step history
    # (never re-writes a step that would lose the latest-step race)
    back = cl.migrate_tenant(dst, moved, src, str(tmp_path))
    assert cl.list_snapshots(str(tmp_path)) == {"hot": 3}
    _assert_state_equal(ref.state_of(a_ref), src.state_of(back), "back")


def test_restore_config_mismatch_is_rejected(small_graph, tmp_path):
    """A snapshot taken at different table dims refuses to restore (clear
    error, no tenant left behind in the target)."""
    g = small_graph
    cfg, params, ef = _setup(g, f=8)
    mgr = cl.ShardedSessionManager(params, ef, model=cfg, mesh="tenant=2")
    tid = mgr.add_tenant()
    cl.snapshot_tenant(mgr, tid, str(tmp_path))

    cfg16 = pl.variant_config("sat+lut+np4", **_dims(g, f=16))
    params16 = tgn.init_params(jax.random.key(0), cfg16)
    other = cl.ShardedSessionManager(params16, ef, model=cfg16,
                                     mesh="tenant=2")
    with pytest.raises(ValueError, match="config fields"):
        cl.restore_tenant(other, str(tmp_path), tid)
    assert other.tenants == ()


def test_sharded_capacity_shrinks_eagerly(small_graph):
    """Cohort slots are released eagerly: stacked rows stay the minimal
    multiple of the tenant axis, and the survivors' states round-trip
    through the shrink untouched."""
    g = small_graph
    cfg, params, ef = _setup(g, key=5)
    mgr = cl.ShardedSessionManager(params, ef, model=cfg, mesh="tenant=2")
    tids = [mgr.add_tenant() for _ in range(3)]
    cohort = mgr.cohort_of(tids[0])
    assert cohort.capacity == 4              # 3 tenants pad to 2x2
    b = next(iter(stream_mod.fixed_count(g, 30)))
    mgr.step({t: b for t in tids})
    keep_states = {t: mgr.state_of(t) for t in tids[1:]}
    mgr.remove_tenant(tids[0])
    assert cohort.capacity == 2              # dead slot + pad released
    assert cohort.state.memory.sharding.spec[0] == "tenant"
    for t in tids[1:]:
        _assert_state_equal(keep_states[t], mgr.state_of(t), msg=t)
    out = mgr.step({t: b for t in tids[1:]})
    assert set(out) == set(tids[1:])


def test_sharded_reserve_live_admission(small_graph):
    """Capacity classes compose with mesh padding: a reserve-enabled
    sharded fleet fast-path attaches/detaches into mesh-aligned spare
    slots (no relayout), stays mesh-sharded, and serves bitwise like the
    exact-size sharded fleet."""
    g = small_graph
    cfg, params, ef = _setup(g, key=5)
    mgr = cl.ShardedSessionManager(params, ef, model=cfg, mesh="tenant=2",
                                   reserve=True)
    a = mgr.add_tenant()
    cohort = mgr.cohort_of(a)
    # ladder says 2, the tenant axis keeps it 2 (already a multiple)
    assert cohort.capacity == 2
    b = mgr.add_tenant()                     # spare slot: fast path
    assert not mgr.last_admission["relayout"]
    assert cohort.capacity == 2
    assert cohort.state.memory.sharding.spec[0] == "tenant"
    feeds = _feeds(g, [a, b], rounds=2)
    for r in range(2):
        mgr.step({t: feeds[t][r] for t in (a, b)})
    mgr.remove_tenant(b)                     # swap-remove: slot idles
    assert not mgr.last_admission["relayout"]
    assert cohort.capacity == 2 and cohort.size == 1
    # survivor bitwise vs the exact-size sharded fleet
    ref = cl.ShardedSessionManager(params, ef, model=cfg, mesh="tenant=2")
    ra, rb = ref.add_tenant(), ref.add_tenant()
    for r in range(2):
        ref.step({ra: feeds[a][r], rb: feeds[b][r]})
    _assert_state_equal(mgr.state_of(a), ref.state_of(ra), msg="survivor")


def test_snapshot_crash_mid_write_recovers(small_graph, tmp_path):
    """A torn write (tmp dir with partial payloads) is invisible to
    restore and garbage-collected by the next snapshot."""
    g = small_graph
    cfg, params, ef = _setup(g)
    mgr = cl.ShardedSessionManager(params, ef, model=cfg, mesh="tenant=2")
    tid = mgr.add_tenant()
    b = next(iter(stream_mod.fixed_count(g, 30)))
    mgr.step({tid: b})
    cl.snapshot_tenant(mgr, tid, str(tmp_path), step=1)
    # simulate a crash mid-snapshot at step 2
    torn = os.path.join(str(tmp_path), tid, "step_00000002.tmp")
    os.makedirs(torn)
    with open(os.path.join(torn, "arr_00000.npy"), "wb") as f:
        f.write(b"\x93NUMPY garbage")
    assert cl.list_snapshots(str(tmp_path)) == {tid: 1}
    fresh = SessionManager(params, ef, model=cfg)
    revived = cl.restore_tenant(fresh, str(tmp_path), tid, name="r")
    _assert_state_equal(mgr.state_of(tid), fresh.state_of(revived), "torn")
    mgr.step({tid: b})
    cl.snapshot_tenant(mgr, tid, str(tmp_path), step=2)   # gc's the tmp
    assert not os.path.exists(torn)
    assert ckpt.latest_step(os.path.join(str(tmp_path), tid)) == 2
