"""Observability layer unit tests (src/repro/obs).

Pure-host tests: metric math (streaming histogram quantiles vs a sorted
list, merge associativity, the defined empty case), registry semantics
(get-or-create, one-type-per-name, atomic snapshot), tracer sampling +
Chrome/Perfetto export shape, SLO burn arithmetic, and the profiler span
helper (``obs.span``) — including one profiler round trip through a tiny
``SessionManager`` on the CPU.
"""
from __future__ import annotations

import glob
import json
import os

import pytest

from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                       RoundTracer, SLOTracker, Span, span)
from repro.obs import trace as obs_trace


# --------------------------------------------------------------- metrics
def test_counter_monotonic():
    c = Counter("x")
    c.inc()
    c.inc(4)
    assert c.snapshot() == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    c.reset()
    assert c.snapshot() == 0


def test_histogram_empty_is_defined():
    h = Histogram("t")
    assert h.count == 0
    assert h.mean() is None
    assert h.quantile(0.5) is None
    assert h.quantile(0.99) is None
    snap = h.snapshot()
    assert snap["count"] == 0 and snap["p99"] is None


def test_histogram_constant_samples_exact():
    # the property the frontend latency test relies on: a histogram of
    # identical values reports that value exactly at every quantile
    # (midpoint clamped to [vmin, vmax])
    h = Histogram("t")
    for _ in range(7):
        h.record(0.011)
    assert h.quantile(0.50) == pytest.approx(0.011)
    assert h.quantile(0.99) == pytest.approx(0.011)
    assert h.mean() == pytest.approx(0.011)
    assert h.count == 7 and h.vmin == h.vmax == 0.011


def test_histogram_quantile_vs_sorted_list():
    # same rank convention as the sorted-list lat[int(q*len)] paths it
    # replaced; value within one bucket ratio (10**(1/32) ~ 7.5%)
    xs = [1e-3 * 1.09 ** i for i in range(120)]
    h = Histogram("t")
    for x in xs:
        h.record(x)
    s = sorted(xs)
    for q in (0.10, 0.50, 0.90, 0.99):
        exact = s[min(len(s) - 1, int(q * len(s)))]
        assert h.quantile(q) == pytest.approx(exact, rel=0.08)


def test_histogram_out_of_range_clamps_to_observed():
    h = Histogram("t")
    h.record(1e-12)                 # below LO -> underflow bucket
    h.record(1e9)                   # above HI -> overflow bucket
    assert h.count == 2
    assert h.quantile(0.0) == pytest.approx(1e-12)    # clamped to vmin
    assert h.quantile(0.99) == pytest.approx(1e9)     # clamped to vmax


def test_histogram_merge_matches_combined():
    a, b, both = Histogram("a"), Histogram("b"), Histogram("ab")
    for i, x in enumerate(0.001 * (1 + i) for i in range(50)):
        (a if i % 2 else b).record(x)
        both.record(x)
    a.merge(b)
    assert a.count == both.count
    assert a.total == pytest.approx(both.total)
    assert a.vmin == both.vmin and a.vmax == both.vmax
    for q in (0.5, 0.9):
        assert a.quantile(q) == pytest.approx(both.quantile(q))


def test_histogram_weighted_record_and_reset():
    h = Histogram("t")
    h.record(0.5, n=10)
    assert h.count == 10 and h.total == pytest.approx(5.0)
    h.reset()
    assert h.count == 0 and h.mean() is None


def test_registry_get_or_create_and_type_binding():
    obs = MetricsRegistry()
    assert obs.counter("a") is obs.counter("a")
    obs.counter("a").inc(3)
    obs.gauge("g").set(7)
    obs.histogram("h").record(0.25)
    with pytest.raises(TypeError):
        obs.gauge("a")              # "a" is bound to Counter
    snap = obs.snapshot()
    assert snap["a"] == 3 and snap["g"] == 7
    assert snap["h"]["count"] == 1
    assert obs.snapshot(prefix="a") == {"a": 3}


def test_registry_merge():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("c").inc(2)
    b.counter("c").inc(5)
    b.gauge("g").set(9)
    b.histogram("h").record(1.0)
    a.merge(b)
    assert a.counter("c").value == 7
    assert a.gauge("g").value == 9
    assert a.histogram("h").count == 1


# ---------------------------------------------------------------- tracer
class _FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_tracer_sampling_cadence():
    tr = RoundTracer(sample_every=4)
    hits = [tr.sample_round() for _ in range(9)]
    assert hits == [True, False, False, False, True,
                    False, False, False, True]
    assert tr.rounds_seen == 9 and tr.rounds_sampled == 3


def test_tracer_would_sample_peeks_without_advancing():
    tr = RoundTracer(sample_every=2)
    assert tr.would_sample() and tr.would_sample()   # no state change
    assert tr.rounds_seen == 0
    assert tr.sample_round() is True
    assert tr.would_sample() is False


def test_tracer_spans_and_bound():
    clk = _FakeClock()
    tr = RoundTracer(clock=clk, max_spans=2)
    with span("session.stage", tr, rows=3):
        clk.t += 0.5
    tr.add("launch", 100.5, 100.6, cat="host")
    tr.add("overflow", 0, 1)
    assert [s.name for s in tr.spans] == ["stage", "launch"]
    assert tr.spans[0].dur == pytest.approx(0.5)
    assert tr.spans[0].args == {"rows": 3}
    assert tr.dropped == 1
    summ = tr.summary()
    assert summ["spans"] == 2 and summ["dropped"] == 1
    assert summ["by_name"]["stage"]["count"] == 1


def test_tracer_chrome_export(tmp_path):
    tr = RoundTracer(clock=_FakeClock())
    tr.add("ingest", 1.0, 1.01, cat="frontend", events=4)
    tr.add("stage", 1.01, 1.02, cat="host")
    tr.add("drain", 1.02, 1.05, cat="device")
    doc = tr.to_chrome()
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert [e["name"] for e in xs] == ["ingest", "stage", "drain"]
    assert xs[0]["ts"] == pytest.approx(1.0e6)       # microseconds
    assert xs[0]["dur"] == pytest.approx(0.01e6)
    # categories land on distinct named tracks
    assert len({e["tid"] for e in xs}) == 3
    assert {m["args"]["name"] for m in metas} >= {"frontend", "host",
                                                  "device"}
    path = tmp_path / "trace.json"
    tr.write_chrome(str(path))
    assert json.loads(path.read_text())["traceEvents"]
    jl = tmp_path / "trace.jsonl"
    tr.write_jsonl(str(jl))
    lines = [json.loads(ln) for ln in jl.read_text().splitlines()]
    assert len(lines) == 3 and lines[0]["name"] == "ingest"
    assert lines[0]["events"] == 4


def test_span_as_dict():
    s = Span("launch", "host", 2.0, 2.5, {"lanes": 2})
    assert s.dur == pytest.approx(0.5)
    assert s.as_dict() == {"name": "launch", "cat": "host", "t0": 2.0,
                           "t1": 2.5, "dur": 0.5, "lanes": 2}


# ------------------------------------------------------- profiler spans
def test_span_records_into_tracer_on_sampled_rounds_only():
    clk = _FakeClock()
    tr = RoundTracer(clock=clk, sample_every=2)
    for _ in range(4):
        trace = tr if tr.sample_round() else None
        with span("session.stage", trace, rows=3, width=8):
            clk.t += 0.5
        with span("session.dispatch", trace, lanes=2):
            clk.t += 0.25
        with span("session.outputs", trace):     # profiler-only span
            clk.t += 1.0
    assert [s.name for s in tr.spans] == ["stage", "launch"] * 2
    stage, launch = tr.spans[:2]
    assert stage.cat == "host" and stage.dur == pytest.approx(0.5)
    assert stage.args == {"rows": 3, "width": 8}
    assert launch.args == {"lanes": 2}
    assert launch.dur == pytest.approx(0.25)
    # recorded spans start where the tracer clock stood at entry
    assert launch.t0 == pytest.approx(stage.t1)


def test_span_names_map_to_the_tracer_taxonomy():
    assert obs_trace.RECORDED_AS == {
        "session.stage": ("stage", "host"),
        "session.dispatch": ("launch", "host"),
        "frontend.flush": ("flush", "frontend")}
    clk = _FakeClock()
    tr = RoundTracer(clock=clk)
    with span("frontend.flush", tr) as fl:
        clk.t += 0.125
        fl.args["tenants"] = 5               # added inside the block
    (s,) = tr.spans
    assert (s.name, s.cat, s.args) == ("flush", "frontend", {"tenants": 5})
    assert fl.t0 == pytest.approx(100.0)


def test_span_without_tracer_is_one_annotation_and_never_fences(monkeypatch):
    import jax
    from jax.profiler import TraceAnnotation

    def fence(*_a, **_k):
        raise AssertionError("span fenced the device")

    monkeypatch.setattr(jax, "block_until_ready", fence)
    for name in ("session.step", "session.stage", "session.stage_wait",
                 "session.dispatch", "session.outputs", "frontend.flush"):
        ann = span(name, None, round=3)
        assert type(ann) is TraceAnnotation
        with ann:
            pass
    # a sampled round records on the tracer's clock, still without a fence
    tr = RoundTracer(clock=_FakeClock())
    with span("session.dispatch", tr if tr.sample_round() else None):
        pass
    assert [s.name for s in tr.spans] == ["launch"]


def _tiny_session(n_tenants=2, f=8):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import pipeline as pl, tgn
    from repro.data import temporal_graph as tgd
    from repro.serving.session import SessionManager

    g = tgd.wikipedia_like(n_edges=200)
    cfg = pl.variant_config("teacher", n_nodes=g.cfg.n_nodes,
                            n_edges=g.n_edges, f_edge=172, f_mem=f,
                            f_time=f, f_emb=f, m_r=4)
    params = tgn.init_params(jax.random.key(0), cfg)
    mgr = SessionManager(params, jnp.asarray(g.edge_feats), model=cfg,
                         use_kernels=False)
    tids = [mgr.add_tenant() for _ in range(n_tenants)]

    def batches(r, b=16):
        lo = r * b
        out = {}
        for i, t in enumerate(tids):
            sl = slice(lo + 40 * i, lo + 40 * i + b)
            eid = np.arange(sl.start, sl.stop, dtype=np.int32)
            out[t] = (g.src[sl], g.dst[sl], eid, g.ts[sl], None)
        return out
    return mgr, batches


def test_unsampled_session_rounds_never_fence(monkeypatch):
    """Two rounds with no tracer attached: the span sites and the round
    dispatch call no ``jax.block_until_ready`` (the stager's reuse gate
    first waits in the third round, on the first round's set)."""
    import jax
    mgr, batches = _tiny_session()
    mgr.step(batches(0))                     # compile outside the patch
    mgr.sync()
    mgr._stager.drain()

    def fence(*_a, **_k):
        raise AssertionError("an unsampled round fenced the device")

    monkeypatch.setattr(jax, "block_until_ready", fence)
    mgr.step(batches(1))
    mgr.step(batches(2))
    monkeypatch.undo()
    mgr.sync()


def test_profiler_round_trip_nests_session_spans(tmp_path):
    """The session's spans land in a real profiler trace, on the host
    plane, nested as step > stage (> stage_wait) / dispatch / outputs,
    once per round, and the step span carries the round's index."""
    import jax
    from jax.profiler import ProfileData
    mgr, batches = _tiny_session()
    for r in range(2):                       # compile and fill both sets
        mgr.step(batches(r))
    mgr.sync()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for r in range(2, 5):
            mgr.step(batches(r))
        mgr.sync()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events.extend((ev.name, ev.start_ns, ev.end_ns,
                               dict(ev.stats)) for ev in line.events
                              if ev.name.startswith("session."))
    steps = sorted((e for e in events if e[0] == "session.step"),
                   key=lambda e: e[1])
    assert [e[3].get("round") for e in steps] == [2, 3, 4]

    def inside(name, outer):
        return [e for e in events if e[0] == name
                and outer[1] <= e[1] and e[2] <= outer[2]]

    for st in steps:
        (stage,) = inside("session.stage", st)
        (dispatch,) = inside("session.dispatch", st)
        (outputs,) = inside("session.outputs", st)
        assert stage[2] <= dispatch[1] and dispatch[2] <= outputs[1]
        # the stager waits on the set of the round before last, the one
        # place the round waits on the device; sync() drained both sets,
        # so the third round after it is the first to wait
        waits = inside("session.stage_wait", stage)
        assert len(waits) == (st[3]["round"] >= 4)


# ------------------------------------------------------------------- slo
def test_slo_burn_math():
    slo = SLOTracker(target_ms=10.0, objective=0.9)
    for _ in range(8):
        slo.observe("t0", 0.005)            # within target
    slo.observe("t0", 0.020, n=2)           # 2 violations
    t = slo.tenant("t0")
    assert t["events"] == 10 and t["violations"] == 2
    assert t["error_rate"] == pytest.approx(0.2)
    # 20% errors against a 10% budget: burning 2x
    assert t["burn_rate"] == pytest.approx(2.0)
    assert t["budget_remaining"] == 0.0
    assert t["observed_p99_ms"] == pytest.approx(20.0, rel=0.08)


def test_slo_zero_observation_tenant_is_full_dict():
    slo = SLOTracker(target_ms=25.0, objective=0.99, source="event")
    t = slo.tenant("never-seen")
    assert t["events"] == 0 and t["violations"] == 0
    assert t["burn_rate"] == 0.0 and t["budget_remaining"] == 1.0
    assert t["observed_p99_ms"] is None
    assert t["source"] == "event"
    assert "never-seen" not in slo.snapshot()   # snapshot = observed only


def test_slo_validation():
    with pytest.raises(ValueError):
        SLOTracker(target_ms=0.0)
    with pytest.raises(ValueError):
        SLOTracker(target_ms=5.0, objective=1.0)
