"""Multi-tenant SessionManager: N concurrent streams through one vmapped
launch must be BITWISE-identical to N sequential single-tenant engines;
sampler backends (uniform / time-decayed reservoir) and the spec-menu
error messages ride along."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pipeline as pl, stages, tgn
from repro.data import stream as stream_mod
from repro.data import temporal_graph as tgd
from repro.serving.engine import StreamingEngine
from repro.serving import session as session_mod
from repro.serving.session import SessionManager


N_TENANTS = 3


@pytest.fixture(scope="module")
def small_graph():
    return tgd.wikipedia_like(n_edges=500)


def _dims(g, f=16):
    return dict(n_nodes=g.cfg.n_nodes, n_edges=g.n_edges, f_edge=172,
                f_mem=f, f_time=f, f_emb=f, m_r=10)


def _tenant_stream(g, i, batch=40, rounds=4):
    """Each tenant replays a different window of the graph (independent
    streams with overlapping vertex populations)."""
    lo = 60 * i
    return stream_mod.fixed_count(g, batch,
                                  window=slice(lo, lo + batch * rounds),
                                  seed=i)


def _assert_state_equal(a, b, msg=""):
    for f in a._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"{msg}:{f}")


# ---------------------------------------------------------------------------
# the acceptance criterion: N-tenant session == N sequential engines, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("variant", ["teacher", "sat+lut+np4"])
def test_multitenant_bitwise_matches_sequential_engines(small_graph, variant):
    """One cohort of N same-variant tenants, advanced by one vmapped launch
    per round, reproduces N independent StreamingEngine runs bitwise —
    trajectories (per-round embeddings) AND final vertex state."""
    g = small_graph
    dims = _dims(g)
    cfg = pl.variant_config(variant, **dims)
    params = tgn.init_params(jax.random.key(0), cfg)
    ef = jnp.asarray(g.edge_feats)

    mgr = SessionManager(params, ef, model=cfg, use_kernels=False)
    tids = [mgr.add_tenant() for _ in range(N_TENANTS)]
    session_embs = {t: [] for t in tids}
    streams = {t: _tenant_stream(g, i) for i, t in enumerate(tids)}
    for _batches, outs in mgr.run(streams):
        for t, o in outs.items():
            session_embs[t].append((np.asarray(o.emb_src),
                                    np.asarray(o.emb_dst)))

    for i, t in enumerate(tids):
        eng = StreamingEngine.from_variant(variant, params, ef,
                                           use_kernels=False, **dims)
        for r, batch in enumerate(_tenant_stream(g, i)):
            hs, hd = eng.process(batch)
            ms, md = session_embs[t][r]
            np.testing.assert_array_equal(ms, np.asarray(hs),
                                          err_msg=f"{t} round {r} src")
            np.testing.assert_array_equal(md, np.asarray(hd),
                                          err_msg=f"{t} round {r} dst")
        _assert_state_equal(mgr.state_of(t), eng.state, msg=t)


def test_mixed_sampler_cohorts_each_match_their_engine(small_graph):
    """Tenants on different sampler backends share the session (and the
    parameter set): one launch per cohort, each tenant still bitwise equal
    to its own sequential engine."""
    g = small_graph
    dims = _dims(g)
    variants = ("sat+lut+np4", "sat+lut+np4+uniform", "sat+lut+np4+reservoir",
                "sat+lut+np4+reservoir")   # two reservoirs: one 2-cohort
    cfg = pl.variant_config("sat+lut+np4", **dims)
    params = tgn.init_params(jax.random.key(1), cfg)
    ef = jnp.asarray(g.edge_feats)

    mgr = SessionManager(params, ef, model=cfg, use_kernels=False)
    tids = [mgr.add_tenant(v) for v in variants]
    assert len(mgr.describe()) == 3         # 3 cohorts for 4 tenants
    streams = {t: _tenant_stream(g, i) for i, t in enumerate(tids)}
    for _batches, _outs in mgr.run(streams):
        pass
    # coalesced (default): the whole 3-cohort round is ONE compiled launch
    assert mgr.metrics[-1]["launches"] == 1

    finals = []
    for i, (t, v) in enumerate(zip(tids, variants)):
        eng = StreamingEngine.from_variant(v, params, ef,
                                           use_kernels=False, **dims)
        for batch in _tenant_stream(g, i):
            eng.process(batch)
        _assert_state_equal(mgr.state_of(t), eng.state, msg=v)
        finals.append(np.asarray(mgr.state_of(t).memory))
    # the sampler policy is load-bearing: different backends on the same
    # stream windows land on different memory states
    assert not np.array_equal(finals[0], finals[1])


def test_stager_reuse_gate_includes_the_consuming_launch(small_graph):
    """``device_put`` on CPU zero-copies aligned host buffers, so a staged
    super-batch can ALIAS the stager's NumPy set: reusing the set two
    rounds later must wait for the launch that consumed it, not just the
    transfer, or the (async) executable reads a torn batch. Pins that
    every step joins its launch outputs into the staged set's reuse gate
    — the race only manifests under scheduler-dependent timing, so the
    gate's shape is asserted directly."""
    g = small_graph
    dims = _dims(g)
    cfg = pl.variant_config("sat+lut+np4", **dims)
    params = tgn.init_params(jax.random.key(0), cfg)
    mgr = SessionManager(params, jnp.asarray(g.edge_feats), model=cfg,
                         use_kernels=False)
    t0 = mgr.add_tenant()
    rounds = list(_tenant_stream(g, 0, batch=20, rounds=3))
    for k, batch in enumerate(rounds):
        mgr.step({t0: batch})
        st = mgr._stager
        gate = st._inflight[st._last]
        # (transfer, consumer-outputs) pair, arrays of the launch output
        assert isinstance(gate, tuple) and len(gate) == 2
        dev, outputs = gate
        assert all(isinstance(x, jax.Array) for x in dev)
        assert any(isinstance(leaf, jax.Array)
                   for leaf in jax.tree_util.tree_leaves(outputs))
    mgr.sync()


def test_idle_tenants_are_bitwise_frozen(small_graph):
    """A round that only some tenants join must not perturb the others:
    the masked (all-invalid) step is a bitwise no-op on their state."""
    g = small_graph
    dims = _dims(g, f=8)
    cfg = pl.variant_config("sat+lut+np4", **dims)
    params = tgn.init_params(jax.random.key(2), cfg)
    mgr = SessionManager(params, jnp.asarray(g.edge_feats), model=cfg)
    a, b = mgr.add_tenant(), mgr.add_tenant()
    batches = list(_tenant_stream(g, 0, rounds=2))
    mgr.step({a: batches[0], b: batches[0]})
    frozen = mgr.state_of(b)
    out = mgr.step({a: batches[1]})          # b idles this round
    assert set(out) == {a}
    _assert_state_equal(mgr.state_of(b), frozen, msg="idle tenant")
    # and the idle round left a's trajectory on the sequential path
    eng = StreamingEngine.from_variant("sat+lut+np4", params,
                                       jnp.asarray(g.edge_feats),
                                       use_kernels=False, **dims)
    for batch in batches:
        eng.process(batch)
    _assert_state_equal(mgr.state_of(a), eng.state, msg="active tenant")


def test_add_tenant_midstream_and_ragged_batches(small_graph):
    """Tenants added after rounds have run start fresh and still match a
    sequential engine; ragged per-tenant batch sizes are padded with masked
    rows (results on real rows unchanged, outputs cut to the real rows)."""
    g = small_graph
    dims = _dims(g, f=8)
    cfg = pl.variant_config("sat+lut+np4", **dims)
    params = tgn.init_params(jax.random.key(3), cfg)
    ef = jnp.asarray(g.edge_feats)
    mgr = SessionManager(params, ef, model=cfg)
    a = mgr.add_tenant()
    first = list(_tenant_stream(g, 0, rounds=2))
    for batch in first:
        mgr.step({a: batch})
    b = mgr.add_tenant()                     # cohort grows mid-serving
    small = next(iter(stream_mod.fixed_count(g, 24, window=slice(0, 24))))
    big = next(iter(stream_mod.fixed_count(g, 40,
                                           window=slice(80, 120), seed=7)))
    outs = mgr.step({b: small, a: big})      # ragged round: B=24 vs B=40
    assert outs[b].emb_src.shape[0] == 24
    assert outs[b].attn_logits.shape[0] == 48
    assert outs[a].emb_src.shape[0] == 40

    eng = StreamingEngine.from_variant("sat+lut+np4", params, ef,
                                       use_kernels=False, **dims)
    hs, _hd = eng.process(small)
    np.testing.assert_array_equal(np.asarray(outs[b].emb_src),
                                  np.asarray(hs))
    _assert_state_equal(mgr.state_of(b), eng.state, msg="late tenant")


def test_kernel_backends_serve_multitenant(small_graph):
    """The Pallas stage backends run under the vmapped cohort launch and
    agree with the reference-backend session within kernel tolerance."""
    g = small_graph
    dims = _dims(g)
    cfg = pl.variant_config("sat+lut+np4", **dims)
    params = tgn.init_params(jax.random.key(4), cfg)
    ef = jnp.asarray(g.edge_feats)
    outs = {}
    for kernels in (True, False):
        mgr = SessionManager(params, ef, model=cfg, use_kernels=kernels)
        tids = [mgr.add_tenant() for _ in range(2)]
        for _b, _o in mgr.run({t: _tenant_stream(g, i, rounds=2)
                               for i, t in enumerate(tids)}):
            pass
        outs[kernels] = [np.asarray(mgr.state_of(t).memory) for t in tids]
    for mk, mr in zip(outs[True], outs[False]):
        np.testing.assert_allclose(mk, mr, atol=2e-5)


def test_remove_tenant_releases_slots_eagerly(small_graph):
    """Removing a tenant shrinks the cohort's stacked tables immediately
    (no dead rows), survivors' states round-trip through the shrink
    bitwise, and a removed tenant's slot is really gone."""
    g = small_graph
    dims = _dims(g, f=8)
    cfg = pl.variant_config("sat+lut+np4", **dims)
    params = tgn.init_params(jax.random.key(6), cfg)
    mgr = SessionManager(params, jnp.asarray(g.edge_feats), model=cfg)
    tids = [mgr.add_tenant() for _ in range(4)]
    cohort = mgr.cohort_of(tids[0])
    batches = list(_tenant_stream(g, 0, rounds=2))
    mgr.step({t: batches[0] for t in tids})
    assert cohort.capacity == 4 == cohort.state.memory.shape[0]
    survivors = {t: mgr.state_of(t) for t in tids if t != tids[1]}
    mgr.remove_tenant(tids[1])               # middle slot: indices shift
    assert cohort.capacity == 3 == cohort.state.memory.shape[0]
    for t, st in survivors.items():
        _assert_state_equal(st, mgr.state_of(t), msg=f"survivor {t}")
    with pytest.raises(KeyError):
        mgr.state_of(tids[1])
    # set_state/state_of round-trip still lands on the right slot
    mgr.set_state(tids[2], survivors[tids[0]])
    _assert_state_equal(mgr.state_of(tids[2]), survivors[tids[0]],
                        msg="set_state after remove")
    out = mgr.step({t: batches[1] for t in survivors})
    assert set(out) == set(survivors)
    # removing the rest tears the cohort down entirely
    for t in survivors:
        mgr.remove_tenant(t)
    assert mgr.tenants == () and cohort.state is None
    assert cohort.capacity == 0


def test_remove_tenant_drains_inflight_rounds(small_graph):
    """Hardening regression: steps are async, so ``remove_tenant`` must
    drain the fleet (``sync``) BEFORE the lane slot is released — a
    dispatched round still reads the stacked tables it launched with.
    Guards both the ordering (drain strictly precedes the slot release)
    and the outcome (survivors of a remove issued right behind
    un-synced steps stay bitwise-correct)."""
    g = small_graph
    dims = _dims(g, f=8)
    cfg = pl.variant_config("sat+lut+np4", **dims)
    params = tgn.init_params(jax.random.key(7), cfg)
    ef = jnp.asarray(g.edge_feats)
    mgr = SessionManager(params, ef, model=cfg)
    tids = [mgr.add_tenant() for _ in range(3)]
    its = {t: iter(_tenant_stream(g, i, rounds=2))
           for i, t in enumerate(tids)}
    for _ in range(2):       # dispatch rounds, never sync: still in flight
        mgr.step({t: next(it) for t, it in its.items()})
    order = []
    cohort = mgr.cohort_of(tids[1])
    orig_sync, orig_remove = mgr.sync, cohort.remove
    mgr.sync = lambda: (order.append("drain"), orig_sync())[-1]
    cohort.remove = lambda t: (order.append("release"),
                               orig_remove(t))[-1]
    mgr.remove_tenant(tids[1])
    mgr.sync, cohort.remove = orig_sync, orig_remove
    assert order == ["drain", "release"]
    for i, t in ((0, tids[0]), (2, tids[2])):
        eng = StreamingEngine.from_variant("sat+lut+np4", params, ef,
                                           use_kernels=False, **dims)
        for batch in _tenant_stream(g, i, rounds=2):
            eng.process(batch)
        _assert_state_equal(mgr.state_of(t), eng.state,
                            msg=f"survivor {t}")


def test_tenant_lifecycle_and_errors(small_graph):
    g = small_graph
    dims = _dims(g, f=8)
    cfg = pl.variant_config("sat+lut+np4", **dims)
    params = tgn.init_params(jax.random.key(5), cfg)
    mgr = SessionManager(params, jnp.asarray(g.edge_feats), model=cfg)
    a = mgr.add_tenant(name="fraud-eu")
    assert mgr.tenants == ("fraud-eu",)
    with pytest.raises(ValueError, match="already exists"):
        mgr.add_tenant(name="fraud-eu")
    # the parameterized axes are shared; samplers/pruning may vary
    with pytest.raises(ValueError, match="shares sat\\+lut parameters"):
        mgr.add_tenant("teacher")
    b = mgr.add_tenant("sat+lut+np4+reservoir", reservoir_tau=3600.0)
    assert "tau=3600" in mgr.cohort_of(b).pipeline.describe()["sampler"]
    # cohorts differing only in tau share a variant name: describe must
    # keep BOTH entries (tau-suffixed), not silently overwrite one
    c = mgr.add_tenant("sat+lut+np4+reservoir", reservoir_tau=60.0)
    taus = {k: v for k, v in mgr.describe().items() if "reservoir" in k}
    assert len(taus) == 2
    assert any(k.endswith("@tau=60") for k in taus)
    assert {t for v in taus.values() for t in v["tenants"]} == {b, c}
    mgr.remove_tenant(c)
    with pytest.raises(KeyError, match="unknown tenants"):
        mgr.step({"nope": next(iter(_tenant_stream(g, 0)))})
    mgr.remove_tenant(a)
    assert mgr.tenants == (b,)
    batch = next(iter(_tenant_stream(g, 0)))
    assert set(mgr.step({b: batch})) == {b}


# ---------------------------------------------------------------------------
# coalesced cross-cohort rounds (one compiled launch per round)
# ---------------------------------------------------------------------------

# the mixed 3-cohort fleet: the prune axis (np4 vs np2) AND a sampler
# cohort, all on the session's DEFAULT parameter set. (A tenant on the
# default set must match its attention+encoder axes; a tenant that brings
# its OWN registered set — register_params + add_tenant(params=...) — may
# vary every axis, the mixed-model tests below.)
MIXED_VARIANTS = ("sat+lut+np4", "sat+lut+np2", "sat+lut+np4+reservoir")


def _mixed_fleet(g, params, cfg, n_tenants, coalesce):
    ef = jnp.asarray(g.edge_feats)
    mgr = SessionManager(params, ef, model=cfg, use_kernels=False,
                         coalesce=coalesce)
    tids = [mgr.add_tenant(MIXED_VARIANTS[i % len(MIXED_VARIANTS)])
            for i in range(n_tenants)]
    return mgr, tids


def test_coalesced_bitwise_matches_percohort_mixed_cohorts(small_graph):
    """A mixed 3-cohort fleet (8 tenants) replays BITWISE-identically
    under the coalesced single-launch round and the per-cohort baseline —
    per-round embeddings, distill views, and final states — through
    ragged batch widths and idle tenants."""
    g = small_graph
    dims = _dims(g, f=8)
    cfg = pl.variant_config("sat+lut+np4", **dims)
    params = tgn.init_params(jax.random.key(7), cfg)
    m1, t1 = _mixed_fleet(g, params, cfg, 8, coalesce=True)
    m2, t2 = _mixed_fleet(g, params, cfg, 8, coalesce=False)
    assert len(m1.describe()) == 3
    rng_widths = (40, 24, 40, 8)          # ragged rounds: stager width grows
    for r, width in enumerate(rng_widths):
        batches = {}
        for i in range(8):
            if r == 2 and i % 4 == 1:     # some tenants idle round 2
                continue
            lo = 50 * i + r * width
            batches[i] = next(iter(stream_mod.fixed_count(
                g, width, window=slice(lo, lo + width), seed=i)))
        o1 = m1.step({t1[i]: b for i, b in batches.items()})
        o2 = m2.step({t2[i]: b for i, b in batches.items()})
        assert set(o1) == {t1[i] for i in batches}
        for i in batches:
            for field in ("emb_src", "emb_dst", "attn_logits",
                          "nbr_valid", "nbr_dt"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(o1[t1[i]], field)),
                    np.asarray(getattr(o2[t2[i]], field)),
                    err_msg=f"round {r} tenant {i} {field}")
    for a, b in zip(t1, t2):
        _assert_state_equal(m1.state_of(a), m2.state_of(b), msg=a)


def test_coalesced_round_is_exactly_one_compiled_launch(small_graph):
    """The launch-count guard: every coalesced ``step`` dispatches exactly
    ONE compiled round execution regardless of cohort count (the
    per-cohort baseline pays one per cohort), and a fleet change relayouts
    without breaking the guarantee."""
    g = small_graph
    dims = _dims(g, f=8)
    cfg = pl.variant_config("sat+lut+np4", **dims)
    params = tgn.init_params(jax.random.key(8), cfg)
    m1, t1 = _mixed_fleet(g, params, cfg, 6, coalesce=True)
    m2, t2 = _mixed_fleet(g, params, cfg, 6, coalesce=False)
    feeds = {i: list(_tenant_stream(g, i, rounds=3)) for i in range(6)}
    for r in range(3):
        before = m1._coalesced.calls if m1._coalesced is not None else 0
        m1.step({t1[i]: feeds[i][r] for i in range(6)})
        m2.step({t2[i]: feeds[i][r] for i in range(6)})
        assert m1._coalesced.calls == before + 1   # ONE compiled execution
        assert m1.metrics[-1]["launches"] == 1
        assert m2.metrics[-1]["launches"] == 3     # baseline: per cohort
    # lane table covers every cohort row: 6 tenants over 3 variants
    assert m1._coalesced.rows == 6
    assert len(set(m1._coalesced.lane_ids.tolist())) == 3
    # fleet change: relayout, still one launch, trajectories still equal
    a1 = m1.add_tenant(MIXED_VARIANTS[0])
    a2 = m2.add_tenant(MIXED_VARIANTS[0])
    assert m1._coalesced is None                   # layout invalidated
    b = next(iter(_tenant_stream(g, 6)))
    m1.step({a1: b})
    m2.step({a2: b})
    assert m1.metrics[-1]["launches"] == 1
    assert m1._coalesced.rows == 7
    _assert_state_equal(m1.state_of(a1), m2.state_of(a2), msg="late tenant")


def test_mixed_kernel_tier_fleet_replays_bitwise(small_graph):
    """One session mixing FUSED and STAGED lanes — same variant on two
    kernel tiers plus a fused reservoir cohort — replays bitwise-
    identically coalesced vs per-cohort vs N solo single-tenant sessions,
    through a ragged round and an idle lane. The fused lanes run the
    single-pass kernel INSIDE the one coalesced launch."""
    g = small_graph
    dims = _dims(g, f=8)
    cfg = pl.variant_config("sat+lut+np4", **dims)
    params = tgn.init_params(jax.random.key(11), cfg)
    ef = jnp.asarray(g.edge_feats)
    lanes = ((None, "fused"), (None, "staged"),
             ("sat+lut+np4+reservoir", "fused"))

    def fleet(coalesce):
        mgr = SessionManager(params, ef, model=cfg, use_kernels="staged",
                             coalesce=coalesce)
        tids = [mgr.add_tenant(v, use_kernels=t) for v, t in lanes]
        return mgr, tids

    m1, t1 = fleet(True)
    m2, t2 = fleet(False)
    # same variant on two tiers = two lanes; reservoir fused = a third
    assert len(m1.describe()) == 3
    tiers = {c.tier for c in m1._cohorts.values()}
    assert tiers == {"fused", "staged"}
    solos = []
    for v, t in lanes:
        m = SessionManager(params, ef, model=cfg, use_kernels="staged")
        solos.append((m, m.add_tenant(v, use_kernels=t)))

    feeds = [list(_tenant_stream(g, i, batch=30, rounds=4))
             for i in range(len(lanes))]
    widths = (30, 18, 30, 30)             # round 1 ragged
    for r, w in enumerate(widths):
        batches = {}
        for i in range(len(lanes)):
            if r == 2 and i == 1:         # staged lane idles round 2
                continue
            b = feeds[i][r]
            batches[i] = stream_mod.EdgeBatch(
                src=b.src[:w], dst=b.dst[:w], eid=b.eid[:w],
                ts=b.ts[:w], valid=b.valid[:w], neg_dst=b.neg_dst[:w])
        o1 = m1.step({t1[i]: b for i, b in batches.items()})
        o2 = m2.step({t2[i]: b for i, b in batches.items()})
        assert m1.metrics[-1]["launches"] == 1
        for i, b in batches.items():
            sm, st = solos[i]
            o3 = sm.step({st: b})[st]
            for field in ("emb_src", "emb_dst", "attn_logits",
                          "nbr_valid", "nbr_dt"):
                a = np.asarray(getattr(o1[t1[i]], field))
                np.testing.assert_array_equal(
                    a, np.asarray(getattr(o2[t2[i]], field)),
                    err_msg=f"round {r} lane {i} {field} (per-cohort)")
                np.testing.assert_array_equal(
                    a, np.asarray(getattr(o3, field)),
                    err_msg=f"round {r} lane {i} {field} (solo)")
    for i in range(len(lanes)):
        sm, st = solos[i]
        _assert_state_equal(m1.state_of(t1[i]), m2.state_of(t2[i]),
                            msg=f"lane {i} coalesced-vs-percohort")
        _assert_state_equal(m1.state_of(t1[i]), sm.state_of(st),
                            msg=f"lane {i} coalesced-vs-solo")


def test_coalesced_outputs_come_from_one_split_per_cohort(small_graph,
                                                          monkeypatch):
    """The coalesced round hands every submitted tenant exactly what
    ``_slice_out`` cuts from the same stacked round — bitwise, in all
    five leaves, ``state`` left out — from ONE compiled split per
    submitting cohort, plus one compiled trim per tenant that submitted
    fewer rows than its cohort's round width. Fused, staged and
    reservoir cohorts of two tenants each, through a ragged round and an
    idle lane; at unchanged widths the split never recompiles."""
    g = small_graph
    dims = _dims(g, f=8)
    cfg = pl.variant_config("sat+lut+np4", **dims)
    params = tgn.init_params(jax.random.key(12), cfg)
    lanes = ((None, "fused"), (None, "staged"),
             ("sat+lut+np4+reservoir", "fused"))
    mgr = SessionManager(params, jnp.asarray(g.edge_feats), model=cfg,
                         use_kernels="staged")
    tids = [mgr.add_tenant(v, use_kernels=t) for v, t in lanes
            for _ in range(2)]             # tenants 2k, 2k+1 share lane k
    assert len(mgr.describe()) == 3

    stacked = []
    launch = pl.CoalescedRound.__call__

    def recording(self, *a, **kw):
        res = launch(self, *a, **kw)
        stacked.append(res[0])
        return res

    monkeypatch.setattr(pl.CoalescedRound, "__call__", recording)
    feeds = [list(_tenant_stream(g, i, batch=30, rounds=6))
             for i in range(len(tids))]
    # round -> {tenant: rows}; absent tenants idle that round
    plan = ({i: 30 for i in range(6)},
            {0: 18, 1: 30, 2: 18, 3: 18, 4: 30, 5: 8},   # ragged
            {0: 30, 4: 30, 5: 30},                       # staged lane idle
            *({i: 30 for i in range(6)},) * 3)           # unchanged widths
    counters = ("session.output_splits", "session.output_trims")
    cache = [session_mod._split_out._cache_size()]
    for r, rows in enumerate(plan):
        batches = {}
        for i, w in rows.items():
            b = feeds[i][r]
            batches[tids[i]] = stream_mod.EdgeBatch(
                src=b.src[:w], dst=b.dst[:w], eid=b.eid[:w], ts=b.ts[:w],
                valid=b.valid[:w], neg_dst=b.neg_dst[:w])
        before = mgr.obs.snapshot()
        calls = mgr._coalesced.calls if mgr._coalesced is not None else 0
        outs = mgr.step(batches)
        after = mgr.obs.snapshot()
        cache.append(session_mod._split_out._cache_size())
        assert mgr._coalesced.calls == calls + 1   # still ONE launch
        assert set(outs) == set(batches)
        submitting, ragged = 0, 0
        for c, out in zip(mgr._cohorts.values(), stacked[-1]):
            mine = [t for t in c.tids if t in batches]
            submitting += bool(mine)
            B = max((batches[t].src.shape[0] for t in mine), default=0)
            for t in mine:
                b = batches[t].src.shape[0]
                ragged += b < B
                want = SessionManager._slice_out(out, c.tids.index(t), b)
                assert outs[t].state is None
                for f in session_mod._OUT_LEAVES:
                    got = getattr(outs[t], f)
                    assert got.shape == getattr(want, f).shape
                    np.testing.assert_array_equal(
                        np.asarray(got), np.asarray(getattr(want, f)),
                        err_msg=f"round {r} {t} {f}")
        assert (submitting, ragged) == ((3, 2) if r == 1 else
                                        (2, 0) if r == 2 else (3, 0))
        assert [after.get(n, 0) - before.get(n, 0) for n in counters] == [
            submitting, ragged], f"round {r}"
    # the three cohorts' stacked outputs share one shape a round: at most
    # one split compile each for the first two rounds' widths (30, then
    # the staged lane's 18), none for the later rounds at seen widths
    compiles = [b - a for a, b in zip(cache, cache[1:])]
    assert max(compiles[:2]) <= 1 and compiles[2:] == [0] * 4, compiles


# ---------------------------------------------------------------------------
# per-lane parameter sets: teacher/student A/B serving in one launch
# ---------------------------------------------------------------------------

# the mixed-MODEL fleet: a teacher lane (different attention+encoder AND
# weights) plus two students on different weight sets — the parameter
# dimension of the lane table. (variant, param-set name or None=default)
MODEL_LANES = (("sat+lut+np4", None),
               ("teacher", "teacher-v1"),
               ("sat+lut+np4", "student-B"))


def _model_fleet_params(g, f=8):
    dims = _dims(g, f=f)
    cfg = pl.variant_config("sat+lut+np4", **dims)
    tcfg = pl.variant_config("teacher", **dims)
    return (dims, cfg, tcfg,
            {None: tgn.init_params(jax.random.key(20), cfg),
             "teacher-v1": tgn.init_params(jax.random.key(21), tcfg),
             "student-B": tgn.init_params(jax.random.key(22), cfg)})


@pytest.mark.parametrize("coalesce", [True, False])
def test_mixed_model_fleet_replays_bitwise(small_graph, coalesce):
    """A teacher lane + two distilled-student lanes in ONE session —
    three parameter sets, two architectures — replay BITWISE-identical
    to three separate per-model SessionManagers, under both the
    coalesced single-launch round and the per-cohort baseline, with the
    launch and retrace counters pinned."""
    g = small_graph
    _dims_, cfg, tcfg, psets = _model_fleet_params(g)
    ef = jnp.asarray(g.edge_feats)

    mgr = SessionManager(psets[None], ef, model=cfg, use_kernels=False,
                         coalesce=coalesce)
    mgr.register_params("teacher-v1", psets["teacher-v1"])
    mgr.register_params("student-B", psets["student-B"])
    tids = [mgr.add_tenant(v, params=p) for v, p in MODEL_LANES]
    assert len(mgr.describe()) == 3
    # same-variant lanes on different weights stay distinct in describe
    assert any(k.endswith("@params=student-B") for k in mgr.describe())

    feeds = {t: list(_tenant_stream(g, i)) for i, t in enumerate(tids)}
    traj = {t: [] for t in tids}
    for r in range(4):
        outs = mgr.step({t: feeds[t][r] for t in tids})
        for t in tids:
            traj[t].append((np.asarray(outs[t].emb_src),
                            np.asarray(outs[t].emb_dst)))
    # the acceptance guard: 3 models advance as ONE compiled launch per
    # round (per-cohort baseline: one per lane), retraced exactly once
    assert mgr.summary()["launches_per_round"] == (1 if coalesce else 3)
    assert {m["launches"] for m in mgr.metrics} == ({1} if coalesce
                                                    else {3})
    if coalesce:
        assert mgr._coalesced.traces == 1
        assert mgr.compile_counters()["round_traces"] == 1

    for i, (t, (v, pname)) in enumerate(zip(tids, MODEL_LANES)):
        ref = SessionManager(psets[pname], ef,
                             model=tcfg if v == "teacher" else cfg,
                             use_kernels=False, coalesce=coalesce)
        rt = ref.add_tenant(name="solo")
        for r in range(4):
            o = ref.step({rt: feeds[t][r]})[rt]
            ms, md = traj[t][r]
            np.testing.assert_array_equal(
                ms, np.asarray(o.emb_src),
                err_msg=f"lane {i} ({v}@{pname}) round {r} src")
            np.testing.assert_array_equal(
                md, np.asarray(o.emb_dst),
                err_msg=f"lane {i} ({v}@{pname}) round {r} dst")
        _assert_state_equal(mgr.state_of(t), ref.state_of(rt),
                            msg=f"lane {i} ({v}@{pname})")
    # and the weights are load-bearing: replaying lane 2's stream under
    # the DEFAULT set (same policy, different weights) diverges from the
    # student-B trajectory the session produced
    base = SessionManager(psets[None], ef, model=cfg, use_kernels=False,
                          coalesce=coalesce)
    bt = base.add_tenant()
    for r in range(4):
        ob = base.step({bt: feeds[tids[2]][r]})[bt]
    assert not np.array_equal(traj[tids[2]][-1][0], np.asarray(ob.emb_src))


def test_param_store_lifecycle_and_errors(small_graph):
    """The registry contract: admission never invents weights (unknown
    names rejected before any lane mutation), registered sets are
    immutable, and a set that does not structurally fit the tenant's
    config is rejected with the leaf-level diff."""
    g = small_graph
    _dims_, cfg, tcfg, psets = _model_fleet_params(g)
    mgr = SessionManager(psets[None], jnp.asarray(g.edge_feats), model=cfg)
    a = mgr.add_tenant()
    with pytest.raises(ValueError, match="unknown param set"):
        mgr.add_tenant(params="nope")
    assert mgr.tenants == (a,)               # rejection mutated nothing
    # byte-identical re-register is a no-op; different content is an error
    mgr.register_params("s", psets["student-B"])
    mgr.register_params("s", psets["student-B"])
    assert mgr.param_store.names() == ("default", "s")
    with pytest.raises(ValueError, match="immutable"):
        mgr.register_params("s", psets["teacher-v1"])
    with pytest.raises(ValueError, match="non-empty string"):
        mgr.register_params("", psets["student-B"])
    # a student set cannot drive a teacher lane (structural mismatch)
    with pytest.raises(ValueError, match="does not fit"):
        mgr.add_tenant("teacher", params="s")
    # without its own weights the teacher still can't join (PR-4 rule)
    with pytest.raises(ValueError, match="shares sat\\+lut parameters"):
        mgr.add_tenant("teacher")
    # digests are stable content fingerprints
    assert mgr.param_store.digest("s") == mgr.param_store.digest("s")
    assert (mgr.param_store.digest("s") !=
            mgr.param_store.digest("default"))


def test_snapshot_restore_preserves_tenant_kernel_tier(small_graph,
                                                       tmp_path):
    """A tenant serving on a non-default kernel tier must RESUME on that
    tier after snapshot/restore: the manifest records the cohort's
    resolved tier (not the session default), and the restored trajectory
    continues bitwise-identically to the unsnapshotted one."""
    from repro.serving.cluster import restore_tenant, snapshot_tenant

    g = small_graph
    dims = _dims(g, f=8)
    cfg = pl.variant_config("sat+lut+np4", **dims)
    params = tgn.init_params(jax.random.key(13), cfg)
    ef = jnp.asarray(g.edge_feats)
    feed = list(_tenant_stream(g, 0, batch=25, rounds=4))

    mgr = SessionManager(params, ef, model=cfg, use_kernels="staged")
    a = mgr.add_tenant(use_kernels="fused")
    mgr.step({a: feed[0]})
    mgr.step({a: feed[1]})
    snapshot_tenant(mgr, a, str(tmp_path), step=2)
    mgr.step({a: feed[2]})
    mgr.step({a: feed[3]})
    mgr.sync()

    other = SessionManager(params, ef, model=cfg, use_kernels="staged")
    b = restore_tenant(other, str(tmp_path), a, name="b")
    assert other.cohort_of(b).tier == "fused"
    other.step({b: feed[2]})
    other.step({b: feed[3]})
    other.sync()
    _assert_state_equal(mgr.state_of(a), other.state_of(b),
                        msg="restored fused lane")


def test_edge_counts_defer_to_summary(small_graph):
    """Steady-state rounds never block on a D2H sync: the per-round edge
    count stays a pending device value in ``metrics`` and is resolved only
    by ``summary()`` (both dispatch modes)."""
    g = small_graph
    dims = _dims(g, f=8)
    cfg = pl.variant_config("sat+lut+np4", **dims)
    params = tgn.init_params(jax.random.key(9), cfg)
    for coalesce in (True, False):
        mgr, tids = _mixed_fleet(g, params, cfg, 3, coalesce=coalesce)
        feeds = {i: list(_tenant_stream(g, i, batch=20, rounds=3))
                 for i in range(3)}
        for r in range(3):
            mgr.step({tids[i]: feeds[i][r] for i in range(3)})
            assert isinstance(mgr.metrics[-1]["edges"], jax.Array), coalesce
        s = mgr.summary()
        # rounds 1..2 (warmup skipped): 2 rounds x 3 tenants x 20 edges
        resolved = sum(int(np.asarray(m["edges"])) for m in mgr.metrics[1:])
        assert resolved == 2 * 3 * 20
        assert s["rounds"] == 2 and s["launches_per_round"] == (
            1 if coalesce else 3)


def test_background_snapshot_writer_bounded_and_durable(small_graph,
                                                        tmp_path):
    """The bounded per-tenant background writer: a submitted snapshot
    restores bitwise after ``wait()``; while a tenant's write is in
    flight further submissions for it are SKIPPED (never queued), so a
    snapshot cadence can never pile IO behind the serving loop."""
    from repro.serving import cluster as cl
    g = small_graph
    dims = _dims(g, f=8)
    cfg = pl.variant_config("sat+lut+np4", **dims)
    params = tgn.init_params(jax.random.key(11), cfg)
    ef = jnp.asarray(g.edge_feats)
    mgr = SessionManager(params, ef, model=cfg)
    a, b = mgr.add_tenant(), mgr.add_tenant()
    batch = next(iter(_tenant_stream(g, 0)))
    mgr.step({a: batch, b: batch})

    w = cl.TenantSnapshotWriter(str(tmp_path))
    assert w.submit(mgr, a, step=1)
    w.wait()
    fresh = SessionManager(params, ef, model=cfg)
    revived = cl.restore_tenant(fresh, str(tmp_path), a, name="r")
    _assert_state_equal(mgr.state_of(a), fresh.state_of(revived),
                        msg="background snapshot")

    class _Stuck:                        # a write that never finishes
        def done(self):
            return False

    w._inflight[b] = _Stuck()
    assert not w.submit(mgr, b, step=1)  # bounded: skipped, not queued
    assert w.skipped == 1
    del w._inflight[b]
    assert w.submit(mgr, b, step=2)      # free again once drained
    w.close()
    assert cl.list_snapshots(str(tmp_path)) == {a: 1, b: 2}

    class _Failed:                       # a write that blew up
        def done(self):
            return True

        def result(self):
            raise IOError("disk full")

    w2 = cl.TenantSnapshotWriter(str(tmp_path))
    w2._inflight["x"] = _Failed()
    w2._inflight["y"] = _Failed()
    with pytest.raises(RuntimeError, match="background snapshot"):
        w2.wait()                        # raises AFTER joining everything
    assert w2._inflight == {}            # ...so nothing is left unjoined
    w2.close()


def test_coalesced_engine_view_and_peek_unchanged(small_graph):
    """The single-tenant engine view: pre-staged device batches take the
    per-cohort fast path (no host round-trip through the stager — the
    prefetched transfer is consumed as-is), still exactly one launch per
    round, and ``peek``'s non-committing output matches ``process``."""
    g = small_graph
    dims = _dims(g, f=8)
    cfg = pl.variant_config("sat+lut+np4", **dims)
    params = tgn.init_params(jax.random.key(10), cfg)
    ef = jnp.asarray(g.edge_feats)
    eng = StreamingEngine.from_variant("sat+lut+np4", params, ef,
                                       use_kernels=False, **dims)
    assert eng.session.coalesce
    batches = list(_tenant_stream(g, 0, rounds=2))
    peeked = eng.session.peek(eng.tid, batches[0])
    hs, _ = eng.process(batches[0])
    np.testing.assert_array_equal(np.asarray(peeked.emb_src),
                                  np.asarray(hs))
    assert eng.session.metrics[-1]["launches"] == 1
    # the engine's device_put-staged batch never bounced through host
    # staging: the session's ring-buffer stager was never even built
    assert eng.session._stager is None


# ---------------------------------------------------------------------------
# sampler backends
# ---------------------------------------------------------------------------


def _one_neighborhood(variant, g, params, state, batch, dims):
    pipe = pl.build_pipeline(variant, **dims)
    vids = jnp.concatenate([jnp.asarray(batch.src), jnp.asarray(batch.dst)])
    t = jnp.concatenate([jnp.asarray(batch.ts), jnp.asarray(batch.ts)])
    return pipe.stages.sampler(params, pipe.prepare(params), state,
                               jnp.asarray(g.edge_feats), vids, t)


@pytest.mark.parametrize("variant", ["sat+lut+np4+uniform",
                                     "sat+lut+np4+reservoir"])
def test_randomized_samplers_select_valid_deterministic(small_graph,
                                                        variant):
    """Both hash-randomized policies pick k slots, only ever valid ones
    (when enough exist), and are deterministic — two identical queries
    sample the identical neighborhood (the property the bitwise session
    guarantee rests on)."""
    g = small_graph
    dims = _dims(g)
    cfg = pl.variant_config(variant, **dims)
    params = tgn.init_params(jax.random.key(0), cfg)
    state = tgn.init_state(cfg)
    ef = jnp.asarray(g.edge_feats)
    batches = list(stream_mod.fixed_count(g, 50, window=slice(0, 200)))
    for batch in batches[:-1]:
        b = tuple(jnp.asarray(x) for x in
                  (batch.src, batch.dst, batch.eid, batch.ts, batch.valid))
        state = tgn.process_batch(params, cfg, state, None, ef, *b).state
    nb1 = _one_neighborhood(variant, g, params, state, batches[-1], dims)
    nb2 = _one_neighborhood(variant, g, params, state, batches[-1], dims)
    np.testing.assert_array_equal(np.asarray(nb1.dt), np.asarray(nb2.dt))
    np.testing.assert_array_equal(np.asarray(nb1.valid),
                                  np.asarray(nb2.valid))
    assert nb1.dt.shape[1] == 4
    # rows with >= k valid ring slots must select k valid ones
    full = np.asarray(nb1.full_valid).sum(axis=1)
    sel = np.asarray(nb1.valid).sum(axis=1)
    assert np.all(sel[full >= 4] == 4)
    assert np.all(sel[full < 4] == full[full < 4])


def test_reservoir_tau_biases_toward_recency(small_graph):
    """As tau -> 0 the reservoir weight exp(-dt/tau) collapses onto the
    most recent neighbors, so the mean selected dt must not exceed the
    uniform policy's."""
    g = small_graph
    dims = _dims(g)
    dims_tau = dict(dims, reservoir_tau=1e-3)
    cfg = pl.variant_config("sat+lut+np4", **dims)
    params = tgn.init_params(jax.random.key(0), cfg)
    state = tgn.init_state(cfg)
    ef = jnp.asarray(g.edge_feats)
    batches = list(stream_mod.fixed_count(g, 50, window=slice(0, 300)))
    for batch in batches[:-1]:
        b = tuple(jnp.asarray(x) for x in
                  (batch.src, batch.dst, batch.eid, batch.ts, batch.valid))
        state = tgn.process_batch(params, cfg, state, None, ef, *b).state
    nb_u = _one_neighborhood("sat+lut+np4+uniform", g, params, state,
                             batches[-1], dims)
    nb_r = _one_neighborhood("sat+lut+np4+reservoir", g, params, state,
                             batches[-1], dims_tau)
    du = np.asarray(nb_u.dt)[np.asarray(nb_u.valid)]
    dr = np.asarray(nb_r.dt)[np.asarray(nb_r.valid)]
    assert dr.mean() <= du.mean()


def test_sampler_variants_run_through_pipeline(small_graph):
    g = small_graph
    dims = _dims(g, f=8)
    for variant in pl.SAMPLER_VARIANTS:
        pipe = pl.build_pipeline(variant, **dims)
        params = pipe.init_params(jax.random.key(0))
        state = pipe.init_state()
        b = next(iter(stream_mod.fixed_count(g, 32)))
        bt = tuple(jnp.asarray(x) for x in
                   (b.src, b.dst, b.eid, b.ts, b.valid))
        out = pipe.step_fn(params, state, bt, jnp.asarray(g.edge_feats))
        assert bool(jnp.all(jnp.isfinite(out.emb_src)))


# ---------------------------------------------------------------------------
# spec menu in error messages
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bad", ["sat+lut+bogus", "nope+cosine", "sat+fft",
                                 "vanilla+cosine+uniform",
                                 "sat+lut+np4+np2+x"])
def test_invalid_spec_prints_the_full_menu(bad):
    with pytest.raises(ValueError) as ei:
        pl.build_pipeline(bad, n_nodes=10, n_edges=10)
    msg = str(ei.value)
    for token in ("vanilla", "sat", "cosine", "lut", "np<k>", "recent",
                  "uniform", "reservoir", "registered variants",
                  "aliases"):
        assert token in msg, f"{token!r} missing from menu for {bad!r}"


def test_sampler_spec_round_trips():
    assert pl.resolve_variant("sat+lut+np4+reservoir").sampler == "reservoir"
    assert pl.resolve_variant("uniform") == pl.VariantSpec(
        "sat", "lut", 4, "uniform")
    assert pl.variant_name(pl.VariantSpec("sat", "lut", 2, "uniform")) == \
        "sat+lut+np2+uniform"
    assert pl.variant_name(pl.resolve_variant("reservoir")) == \
        "sat+lut+np4+reservoir"
    # default sampler stays out of canonical names
    assert pl.variant_name(pl.VariantSpec("sat", "lut", 4)) == "sat+lut+np4"
    assert stages.SAMPLERS == ("recent", "uniform", "reservoir")
    # an explicit 'recent' clause is the default policy: legal anywhere,
    # and it still arms the duplicate-clause check in BOTH orders
    assert pl.resolve_variant("vanilla+cosine+recent").sampler == "recent"
    for dup in ("sat+lut+recent+uniform", "sat+lut+uniform+recent"):
        with pytest.raises(ValueError, match="duplicate sampler"):
            pl.resolve_variant(dup)


# ---------------------------------------------------------------------------
# observability: registry-backed compile counters under live admission
# ---------------------------------------------------------------------------


def test_reserve_mode_compile_counters_frozen_across_admission(small_graph):
    """The registry-backed compile counters are FROZEN across reserve-mode
    attach-detach-attach cycles that land in spare lane slots (serving
    rounds between each mutation), and a forced relayout — exhausting the
    capacity class — increments ``relayouts`` exactly once."""
    g = small_graph
    dims = _dims(g, f=8)
    cfg = pl.variant_config("sat+lut+np4", **dims)
    params = tgn.init_params(jax.random.key(21), cfg)
    ef = jnp.asarray(g.edge_feats)
    mgr = SessionManager(params, ef, model=cfg, use_kernels=False,
                         reserve=True)
    tids = [mgr.add_tenant(name=f"t{i}") for i in range(3)]
    feeds = list(_tenant_stream(g, 0, batch=20, rounds=10))

    def step(r):
        mgr.step({t: feeds[r] for t in mgr.tenants})

    step(0)
    step(1)
    c0 = mgr.compile_counters()
    assert c0["round_traces"] == 1         # one compiled round, reused
    assert c0["relayouts"] == mgr.relayouts  # registry mirrors the legacy

    # attach -> step -> detach -> step -> attach -> step: all spare-slot
    # fast paths (3 tenants in a capacity-4 class), counters pinned
    extra = mgr.add_tenant(name="late")
    step(2)
    mgr.remove_tenant(extra)
    step(3)
    extra = mgr.add_tenant(name="later")
    step(4)
    c1 = mgr.compile_counters()
    assert c1["relayouts"] == c0["relayouts"]
    assert c1["round_traces"] == c0["round_traces"]
    assert {m["launches"] for m in mgr.metrics} == {1}

    # force a relayout: a 5th resident tenant exhausts the class of 4
    mgr.add_tenant(name="overflow")
    assert mgr._coalesced is None          # layout invalidated...
    step(5)
    step(6)
    c2 = mgr.compile_counters()
    assert c2["relayouts"] == c1["relayouts"] + 1   # ...rebuilt ONCE
    assert c2["round_traces"] == 1         # fresh launch, one trace
    assert mgr.relayouts == c2["relayouts"]
    assert len(tids) + 2 == len(mgr.tenants)
