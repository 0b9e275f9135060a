#!/usr/bin/env python
"""Bring-up smoke: the served TGN path on one TPU chip, at Wikipedia scale.

    python chip_smoke.py              # one chip: the served path
    python chip_smoke.py --chips 4    # four chips: the sharded tenant fabric

One chip. A Wikipedia-shaped stream (9,227 vertices, 157,474 edges, 172-d
edge features) is generated from a seed, and the paper's widths are served
(f_mem = f_time = f_emb = 100, m_r = 10). The server is built the way
``python -m repro.launch.serve --listen`` builds it: a ``SessionManager``
with a ``CapacityLadder`` reserve, a ``ServingFrontend`` and the JSON-lines
transport on ``127.0.0.1:0``. An asyncio client in this process attaches
four lanes (fused np4, fused np4+reservoir, staged np2, and the teacher on
the reference tier under its own parameter set), ingests a few thousand
events, cuts every round with ``flush`` so each round carries every lane at
the same width, and reads ``stats`` and ``metrics``. All lanes share one
compiled round. Every tenant's final memory table and last-round embeddings
are then compared with the float32 reference (``core/tgn.process_batch``
under highest matmul precision) replaying the frontend's round log on the
same chip.

Four chips (``--chips 4``). Only the sharded tenant fabric: the same lanes
on ``ShardedSessionManager`` meshes ``tenant=4`` and ``tenant=2,vertex=2``,
each compared on the same batches with an unsharded ``SessionManager`` on
one device of this process, plus a check that a cohort's state spans all
four devices.

Every earlier line is a report; the last line is one JSON object naming the
device. The script exits non-zero, and prints no result, when JAX finds no
TPU or any phase fails. Everything runs in this one process: a chip belongs
to the process that first touches it.
"""
from __future__ import annotations

import argparse
import asyncio
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if not os.path.isdir(os.path.join(HERE, "src", "repro")):
    sys.exit("chip_smoke.py runs from a checkout of the repository "
             "(src/repro not found next to it)")
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import pipeline as pl, tgn  # noqa: E402
from repro.data import temporal_graph as tgd  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.serving.admission import CapacityLadder  # noqa: E402
from repro.serving.frontend import (  # noqa: E402
    FrontendConfig, ServingFrontend, serve_jsonl)
from repro.serving.session import SessionManager  # noqa: E402
from repro.utils import use_compile_cache  # noqa: E402

#: The public JODIE Wikipedia interaction set: 8,227 users, 1,000 pages,
#: 157,474 edges, 172-d edge features.
WIKIPEDIA = tgd.StreamConfig(n_users=8227, n_items=1000, n_edges=157_474,
                             f_edge=172)
#: The paper's widths.
WIDTHS = dict(f_mem=100, f_time=100, f_emb=100, m_r=10)

#: (lane name, variant, kernel tier, parameter set); None = the default
#: (student) set.
LANES = (("np4-fused", "sat+lut+np4", "fused", None),
         ("np4-reservoir-fused", "sat+lut+np4+reservoir", "fused", None),
         ("np2-staged", "sat+lut+np2", "staged", None),
         ("teacher-ref", "vanilla+cosine", "ref", "teacher"))
#: The four-chip phase: two lanes, two tenants each, fill a tenant=4 mesh.
SHARDED_LANES = (("np4-fused-a", "sat+lut+np4", "fused", None),
                 ("np4-fused-b", "sat+lut+np4", "fused", None),
                 ("np2-staged-a", "sat+lut+np2", "staged", None),
                 ("np2-staged-b", "sat+lut+np2", "staged", None))
MESHES = ("tenant=4", "tenant=2,vertex=2")

#: Tolerances against the float32 reference, as the largest absolute error
#: over a tenant's memory table (values in (-1, 1)) and over its last
#: round's embeddings divided by their largest magnitude. The served lanes
#: run at the backend's default matmul precision, which on a TPU is one
#: bfloat16 pass (relative error ~2^-9 per product), compounded over the
#: recurrent memory updates of every round.
TOL = {"memory_abs": 2e-2, "emb_rel": 2e-2}
#: Sharded vs unsharded: the same programs on different devices.
#: Only float32 accumulation order may differ.
SHARDED_TOL = {"memory_abs": 1e-4, "emb_rel": 1e-4}


def make_graph(cfg: tgd.StreamConfig = WIKIPEDIA) -> tgd.TemporalGraph:
    return tgd.generate(cfg)


def _configs(graph, widths):
    dims = dict(n_nodes=graph.cfg.n_nodes, n_edges=graph.n_edges,
                f_edge=graph.cfg.f_edge, **widths)
    return (pl.variant_config("sat+lut+np4", **dims),
            pl.variant_config("vanilla+cosine", **dims))


def _params(graph, widths, seed):
    """Random student and teacher weights made from ``seed``; the LUT
    boundaries are fitted to the stream's own inter-event gaps."""
    student_cfg, teacher_cfg = _configs(graph, widths)
    gaps = np.diff(graph.ts).astype(np.float64)
    key = jax.random.key(seed)
    student = tgn.init_params(key, student_cfg, dt_samples=gaps[gaps > 0])
    teacher = tgn.init_params(jax.random.fold_in(key, 1), teacher_cfg)
    return student_cfg, student, teacher


def _tenant_batches(graph, lanes, rounds, batch):
    """Each lane's tenant replays its own contiguous slice of the stream:
    ``{name: [(src, dst, eid, ts), ...]}`` with ``rounds`` batches."""
    cols = (graph.src, graph.dst, np.arange(graph.n_edges, dtype=np.int32),
            graph.ts)
    out = {}
    for i, (name, *_rest) in enumerate(lanes):
        lo = i * rounds * batch
        assert lo + rounds * batch <= graph.n_edges, "stream too short"
        out[name] = [tuple(x[lo + r * batch:lo + (r + 1) * batch]
                           for x in cols) for r in range(rounds)]
    return out


def _max_errors(got_mem, want_mem, got_emb, want_emb) -> dict:
    got_mem, want_mem = np.asarray(got_mem), np.asarray(want_mem)
    got_emb, want_emb = np.asarray(got_emb), np.asarray(want_emb)
    scale = max(float(np.max(np.abs(want_emb))), 1e-30)
    return {"memory_abs": float(np.max(np.abs(got_mem - want_mem))),
            "emb_rel": float(np.max(np.abs(got_emb - want_emb))) / scale}


def _within(errs: dict, tol: dict) -> bool:
    return all(errs[k] <= tol[k] for k in tol)


# ---------------------------------------------------------------------------
# one chip: the served path
# ---------------------------------------------------------------------------


async def _drive(fe: ServingFrontend, lanes, batches, log) -> dict:
    """The wire session: attach every lane, ingest each round's events,
    flush, then read stats and metrics. Any refused request fails the run.
    Returns ``{"tids", "flush_s", "stats", "metrics"}``."""
    await fe.start()
    server = await serve_jsonl(fe, "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    reader, writer = await asyncio.open_connection("127.0.0.1", port)

    async def call(req):
        writer.write(json.dumps(req).encode() + b"\n")
        await writer.drain()
        resp = json.loads(await reader.readline())
        if not resp.get("ok"):
            raise RuntimeError(f"{req.get('op')} refused: {resp}")
        return resp

    try:
        tids = {}
        for name, variant, tier, pset in lanes:
            resp = await call({"op": "attach", "variant": variant,
                               "name": name, "use_kernels": tier,
                               **({"params": pset} if pset else {})})
            tids[name] = resp["tid"]
        flush_s, n_ingest = [], 0
        rounds = len(next(iter(batches.values())))
        for r in range(rounds):
            for name, tid in tids.items():
                for src, dst, eid, ts in zip(*batches[name][r]):
                    await call({"op": "ingest", "tid": tid, "src": int(src),
                                "dst": int(dst), "eid": int(eid),
                                "ts": float(ts)})
                    n_ingest += 1
            t0 = time.perf_counter()
            resp = await call({"op": "flush"})
            flush_s.append(time.perf_counter() - t0)
            if sorted(resp["flushed"]) != sorted(tids.values()):
                raise RuntimeError(f"round {r} flushed {resp['flushed']}, "
                                   f"not every lane")
        stats = (await call({"op": "stats"}))["stats"]
        metrics = (await call({"op": "metrics"}))["metrics"]
        log(f"wire: {len(tids)} attach, {n_ingest} ingest, {rounds} flush, "
            f"stats, metrics — all ok")
    finally:
        writer.close()
        server.close()
        await server.wait_closed()
        await fe.stop()
    return {"tids": tids, "flush_s": flush_s, "stats": stats,
            "metrics": metrics}


def reference_errors(mgr: SessionManager, fe: ServingFrontend, served: dict,
                     edge_feats, tids: dict) -> dict:
    """Replay each tenant's rounds from the frontend's round log through
    the float32 reference at highest matmul precision, and compare its
    final memory table and last-round embeddings with what was served.
    Returns ``{lane: {"memory_abs", "emb_rel"}}``."""
    errs = {}
    with jax.default_matmul_precision("highest"):
        for name, tid in tids.items():
            cohort = mgr.cohort_of(tid)
            cfg, params = cohort.cfg, cohort.params
            ref = jax.jit(functools.partial(tgn.process_batch, cfg=cfg,
                                            node_feats=None))
            state = tgn.init_state(cfg)
            for rnd in fe.round_log:
                b = rnd[tid]
                out = ref(params, state=state, edge_feats=edge_feats,
                          src=jnp.asarray(b.src), dst=jnp.asarray(b.dst),
                          eid=jnp.asarray(b.eid), ts=jnp.asarray(b.ts),
                          valid=jnp.asarray(b.valid))
                state = out.state
            ok = np.asarray(fe.round_log[-1][tid].valid)
            got, want = served[tid], out
            errs[name] = _max_errors(
                mgr.state_of(tid).memory, state.memory,
                np.concatenate([np.asarray(got.emb_src)[ok],
                                np.asarray(got.emb_dst)[ok]]),
                np.concatenate([np.asarray(want.emb_src)[ok],
                                np.asarray(want.emb_dst)[ok]]))
    return errs


def served_phase(graph, widths=WIDTHS, *, rounds: int = 5, batch: int = 200,
                 seed: int = 0, log=print) -> dict:
    """Serve ``LANES`` over the wire and check them against the reference.

    Returns a report: resolved tiers, compile counters, launches per
    round, kernel launches and ``tpu_custom_call`` count of the compiled
    round, compile seconds, and per-lane errors with ``ok`` flags."""
    compile_s = []

    def on_duration(event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        return _served(graph, widths, rounds, batch, seed, log, compile_s)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def _served(graph, widths, rounds, batch, seed, log, compile_s) -> dict:
    student_cfg, student, teacher = _params(graph, widths, seed)
    edge_feats = jnp.asarray(graph.edge_feats)
    mgr = SessionManager(student, edge_feats, model=student_cfg,
                         reserve=CapacityLadder())
    mgr.register_params("teacher", teacher)
    # rounds are cut by the client's flush: the size trigger (max_rows)
    # and the deadline never fire first, and pad_quantum == batch keeps
    # every lane at one width, so the fleet runs one executable
    fe = ServingFrontend(mgr, FrontendConfig(max_wait_s=3600.0,
                                             max_rows=2 * batch,
                                             queue_rows=4 * batch,
                                             pad_quantum=batch),
                         record_rounds=True)
    served = {}
    step = mgr.guarded_step

    def recording_step(batches):
        outs = step(batches)
        served.update(outs)            # every tenant's latest round
        return outs

    mgr.guarded_step = recording_step
    batches = _tenant_batches(graph, LANES, rounds, batch)
    ops.reset_launch_count()
    wire = asyncio.run(_drive(fe, LANES, batches, log))
    n_launch = ops.launch_count()     # kernel launches traced into the round
    tids = wire["tids"]
    mgr.sync()

    desc = mgr.describe()
    tiers = {name: mgr.cohort_of(tid).tier for name, tid in tids.items()}
    log("lanes (variant -> resolved tier): " + ", ".join(
        f"{k} -> {v['tier']}" for k, v in desc.items()))
    counters = mgr.compile_counters()
    launches = sorted({m["launches"] for m in mgr.metrics})
    log(f"compile_counters: {counters}; launches per round: "
        f"{set(launches)}")
    log(f"round walls seen by the client (host clock, first includes "
        f"compilation): {wire['flush_s']}")
    n_custom = mgr.lower_round(batch).compile().as_text().count(
        "tpu_custom_call")
    log(f"compiled round: tpu_custom_call x{n_custom}; "
        f"ops.launch_count() = {n_launch}")
    log(f"backend compile seconds: {sum(compile_s)} over {len(compile_s)} "
        f"compiles, the largest {max(compile_s)}")

    errs = reference_errors(mgr, fe, served, edge_feats, tids)
    lanes_ok = {}
    for name, e in errs.items():
        lanes_ok[name] = _within(e, TOL)
        log(f"lane {name} [{tiers[name]}]: memory max abs err "
            f"{e['memory_abs']} (tol {TOL['memory_abs']}), embedding max "
            f"abs err / max |ref| {e['emb_rel']} (tol {TOL['emb_rel']}) "
            f"-> {'ok' if lanes_ok[name] else 'OUT OF TOLERANCE'}")
    return {"tiers": tiers, "counters": counters, "launches": launches,
            "n_custom": n_custom, "n_launch": n_launch,
            "compile_s": sum(compile_s), "errors": errs,
            "lanes_ok": lanes_ok, "stats": wire["stats"],
            "metrics": wire["metrics"]}


def check_served(report: dict) -> list:
    """What the one-chip run must show; returns the failures."""
    want = {name: tier for name, _v, tier, _p in LANES}
    fails = []
    if report["tiers"] != want:
        fails.append(f"resolved tiers {report['tiers']} != {want}")
    if report["counters"]["round_traces"] != 1:
        fails.append(f"round_traces {report['counters']['round_traces']}")
    if report["launches"] != [1]:
        fails.append(f"launches per round {report['launches']}")
    if report["n_custom"] < 1:
        fails.append("no tpu_custom_call in the compiled round")
    fails += [f"lane {n} out of tolerance" for n, ok
              in report["lanes_ok"].items() if not ok]
    return fails


# ---------------------------------------------------------------------------
# four chips: the sharded tenant fabric
# ---------------------------------------------------------------------------


def _fleet(mgr, lanes, teacher):
    if any(p == "teacher" for *_x, p in lanes):
        mgr.register_params("teacher", teacher)
    return {name: mgr.add_tenant(variant, name=name, use_kernels=tier,
                                 params=pset)
            for name, variant, tier, pset in lanes}


def _run_fleet(mgr, tids, batches):
    from repro.data.stream import EdgeBatch
    outs = {}
    rounds = len(next(iter(batches.values())))
    for r in range(rounds):
        step = {}
        for name, tid in tids.items():
            src, dst, eid, ts = batches[name][r]
            z = np.zeros_like(src)
            step[tid] = EdgeBatch(src, dst, eid, ts,
                                  np.ones(src.shape, bool), z)
        outs = mgr.step(step)
    mgr.sync()
    return outs


def sharded_phase(graph, widths=WIDTHS, *, rounds: int = 3,
                  batch: int = 200, seed: int = 0, meshes=MESHES,
                  log=print) -> dict:
    """``SHARDED_LANES`` on each mesh of ``meshes`` vs an unsharded
    session on one device. Returns ``{mesh: {"devices", "errors",
    "ok"}}``."""
    from repro.serving.cluster import ShardedSessionManager
    student_cfg, student, teacher = _params(graph, widths, seed)
    edge_feats = jnp.asarray(graph.edge_feats)
    batches = _tenant_batches(graph, SHARDED_LANES, rounds, batch)

    base = SessionManager(student, edge_feats, model=student_cfg)
    base_tids = _fleet(base, SHARDED_LANES, teacher)
    base_outs = _run_fleet(base, base_tids, batches)
    report = {}
    for spec in meshes:
        mgr = ShardedSessionManager(student, edge_feats, model=student_cfg,
                                    mesh=spec)
        tids = _fleet(mgr, SHARDED_LANES, teacher)
        outs = _run_fleet(mgr, tids, batches)
        devices = {len(mgr.cohort_of(t).state.memory.sharding.device_set)
                   for t in tids.values()}
        errs = {}
        for name, tid in tids.items():
            got, want = outs[tid], base_outs[base_tids[name]]
            errs[name] = _max_errors(
                mgr.state_of(tid).memory,
                base.state_of(base_tids[name]).memory,
                np.concatenate([got.emb_src, got.emb_dst]),
                np.concatenate([want.emb_src, want.emb_dst]))
        ok = (all(_within(e, SHARDED_TOL) for e in errs.values())
              and devices == {len(jax.devices())})
        log(f"mesh {spec}: cohort states span {devices} devices; "
            + "; ".join(f"{n}: memory {e['memory_abs']}, emb "
                        f"{e['emb_rel']}" for n, e in errs.items())
            + f" (tol {SHARDED_TOL}) -> {'ok' if ok else 'FAILED'}")
        report[spec] = {"devices": devices, "errors": errs, "ok": ok}
    return report


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served path on one chip (default); "
                         "4: only the sharded tenant fabric on four")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(jax.devices())}", file=sys.stderr)
        return 1
    cache = use_compile_cache()
    print(f"device_kind {dev.device_kind!r}, {len(jax.devices())} "
          f"device(s); compile cache {cache}", flush=True)
    t0 = time.perf_counter()
    graph = make_graph()
    print(f"graph: {graph.cfg.n_nodes} vertices, {graph.n_edges} edges, "
          f"{graph.cfg.f_edge}-d edge features "
          f"({time.perf_counter() - t0} s on the host)", flush=True)
    if args.chips == 4:
        report = sharded_phase(graph)
        fails = [f"mesh {m} failed" for m, r in report.items()
                 if not r["ok"]]
    else:
        fails = check_served(served_phase(graph))
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    n_cache = sum(len(f) for _d, _s, f in os.walk(cache))
    print(f"compile cache {cache}: {n_cache} entries")
    if fails:
        print("chip_smoke FAILED: " + "; ".join(fails), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
