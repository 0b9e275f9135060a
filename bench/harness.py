"""The benchmark's cell logic: build a cell from its files and a seed,
warm it up, measure its window, and check what the window produced
against the plain reference.

``run_cell`` is everything a run does after ``run.py`` has found the chip;
it runs on any backend, which is how the tests rehearse it on the CPU.
What belongs to one configuration, traffic mix or per-layer metric is in
files of its own (``configs/``, ``traffic/``, ``metrics/``), found by the
names ``BENCHMARK.json`` gives.
"""
from __future__ import annotations

import asyncio
import contextlib
import gc
import importlib.util
import json
import os
import queue
import subprocess
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import counts  # noqa: E402
import peaks  # noqa: E402
import stream  # noqa: E402
import trace_reduce  # noqa: E402


# ---------------------------------------------------------------------------
# the benchmark's files
# ---------------------------------------------------------------------------


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def traffic_file(name: str) -> str:
    return os.path.join(HERE, "traffic", f"{name}.json")


def metric_reader(name: str):
    """The reader of per-layer metric ``name``: ``metrics/<name>.py``'s
    ``read(record) -> float | None``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that cell ``cell``
    reports: those that list it, and those that list no cell."""
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


def resolve(spec: dict, cell_name: str) -> tuple:
    """``(cell, config, traffic)`` of a cell, each read from its file."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json; "
                       f"there are {sorted(cells)}")
    cell = cells[cell_name]
    confs = {c["name"]: c for c in spec["configs"]}
    cfg = load_json(os.path.join(ROOT, confs[cell["config"]]["file"]))
    traffic = load_json(traffic_file(cell["traffic"]))
    return cell, cfg, traffic


def end_to_end(spec, rec) -> dict:
    values = {"setup_s": rec["setup_s"],
              "events_per_s": rec.get("events_per_s"),
              "p50_latency_ms": rec.get("p50_latency_ms"),
              "p99_latency_ms": rec.get("p99_latency_ms")}
    out = {}
    for m in cell_metrics(spec, rec["cell"], "end_to_end"):
        if values.get(m["name"]) is None:
            raise RuntimeError(f"end-to-end metric {m['name']} was not "
                               f"measured")
        out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def per_layer(spec, rec) -> dict:
    out = {}
    for m in cell_metrics(spec, rec["cell"], "per_layer"):
        v = metric_reader(m["name"])(rec)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# building a cell
# ---------------------------------------------------------------------------


def tenants(cfg: dict) -> list:
    """One entry per tenant, lanes in file order: ``(tid, lane)``."""
    out = []
    for lane in cfg["lanes"]:
        for _ in range(lane["count"]):
            out.append((f"t{len(out)}", lane))
    return out


def lane_key(lane: dict) -> tuple:
    return (lane["variant"], lane["tier"], lane["params"])


def ref_lane(lane: dict, widths: dict) -> dict:
    """What the plain reference needs to know of a lane."""
    return {"kind": lane["params"], "k": lane.get("k", widths["m_r"]),
            "sampler": lane.get("sampler", "recent"),
            "tau": float(lane.get("reservoir_tau", 86400.0)),
            "heads": widths["n_heads"]}


def build(cfg: dict, seed: int, graph, log):
    """The program's session for ``cfg``: weights made from ``seed`` on the
    device, every tenant attached. Each cohort is laid out once, at the
    number of tenants it will hold (``FleetCapacity``), and the rest of
    its tenants attach into its slots: attached one by one with an exact
    layout, n tenants would lay a cohort out n times, a compile each.
    Returns ``(mgr, weights, tids)``."""
    import jax.numpy as jnp
    from repro.core import pipeline as pl
    from repro.serving.session import SessionManager
    import reference

    w = cfg["widths"]
    kinds = sorted({lane["params"] for lane in cfg["lanes"]})
    bounds = reference.lut_boundaries(stream.vertex_gaps(graph),
                                      w["lut_entries"])
    weights = reference.make_weights(seed, kinds, w, graph.shape.f_edge,
                                     bounds)
    first = cfg["lanes"][0]
    dims = dict(n_nodes=graph.shape.n_nodes, n_edges=graph.n_edges,
                f_edge=graph.shape.f_edge, f_mem=w["f_mem"],
                f_time=w["f_time"], f_emb=w["f_emb"], m_r=w["m_r"],
                n_heads=w["n_heads"], lut_entries=w["lut_entries"])
    model = pl.variant_config(first["variant"], **dims)
    fleet = FleetCapacity()
    mgr = SessionManager(weights[first["params"]],
                         jnp.asarray(graph.edge_feats), model=model,
                         reserve=fleet)
    for kind in kinds:
        if kind != first["params"]:
            mgr.register_params(kind, weights[kind])
    def cohort(lane):
        return (*lane_key(lane), lane.get("reservoir_tau"))
    holds = {}
    for _tid, lane in tenants(cfg):
        holds[cohort(lane)] = holds.get(cohort(lane), 0) + 1
    tids = []
    for tid, lane in tenants(cfg):
        fleet.target = holds[cohort(lane)]
        mgr.add_tenant(lane["variant"], name=tid, use_kernels=lane["tier"],
                       params=(None if lane["params"] == first["params"]
                               else lane["params"]),
                       reservoir_tau=lane.get("reservoir_tau"))
        tids.append(tid)
    fleet.target = 0
    spare = sum(v["capacity"] for v in mgr.describe().values()) - len(tids)
    if spare:
        raise RuntimeError(f"{spare} lane slots left spare")
    log("lanes: " + ", ".join(f"{k} -> tier {v['tier']}, capacity "
                              f"{v['capacity']}"
                              for k, v in mgr.describe().items()))
    return mgr, weights, tids


class FleetCapacity:
    """The session's capacity policy (its ``reserve``) while a fleet is
    built: a cohort is laid out for ``target`` tenants, the number it
    will hold, or for the tenants it has if that is more."""

    def __init__(self):
        self.target = 0

    def capacity_for(self, n_tenants: int) -> int:
        return max(n_tenants, self.target)


def _sampled(r: int, seed: int) -> bool:
    """Rounds whose embeddings are kept for the check: about one in 24,
    drawn from the seed."""
    return (r * 2654435761 + seed * 40503) % 24 == 0


class CompileCounter:
    """Counts backend compiles and persistent-cache reads while armed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        self.armed = False
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_kw):
        if self.armed and event in self.EVENTS:
            self.n += 1
            self.seconds += secs

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on)


class Tracer:
    """The profiler around the window (``--trace 1``), and the
    benchmark's own host spans on its clock."""

    def __init__(self, on: bool, out_dir: str):
        import jax
        self.on = on
        self.out_dir = out_dir
        self._jax = jax

    def span(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        return self._jax.profiler.TraceAnnotation(name)

    def start(self):
        if self.on:
            opts = self._jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            self._jax.profiler.start_trace(self.out_dir,
                                           profiler_options=opts)

    def stop(self):
        if self.on:
            self._jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# closed loop: a backlog of every tenant's next batch
# ---------------------------------------------------------------------------


def run_backlog(mgr, tids, graph, cfg, traffic, seed, seconds, tracer,
                compiles, log, t_process):
    B = cfg["batch"]
    replays = [stream.TenantReplay(graph, s)
               for s in stream.tenant_starts(graph, len(tids))]
    ones = np.ones(B, bool)
    log_rounds, kept = [], {}

    def next_round():
        return {tid: (*rp.take(B), ones) for tid, rp in zip(tids, replays)}

    def one_round():
        with tracer.span("bench.next_round"):
            batches = next_round()
        with tracer.span("bench.step"):
            outs = mgr.guarded_step(batches)
        r = len(log_rounds)
        log_rounds.append(batches)
        if _sampled(r, seed):
            kept[r] = outs
        return outs

    for _ in range(int(traffic["warmup_rounds"])):
        one_round()
    mgr.sync()
    n_warm = len(log_rounds)
    n_metrics = len(mgr.metrics)
    tracer.start()
    compiles.armed = True
    with tracer.span("bench.window"):
        mgr.sync()
        t0 = time.monotonic()
        setup_s = t0 - t_process
        while time.monotonic() - t0 < seconds:
            outs = one_round()
        with tracer.span("bench.drain"):
            mgr.sync()
        t1 = time.monotonic()
    compiles.armed = False
    tracer.stop()
    kept[len(log_rounds) - 1] = outs
    n_window = len(log_rounds) - n_warm
    events = n_window * B * len(tids)
    host_step = [m["latency_s"] for m in mgr.metrics[n_metrics:]]
    log(f"window: {n_window} rounds of {len(tids)} tenants x {B} edges in "
        f"{t1 - t0} s; warm-up {n_warm} rounds")
    return {"setup_s": setup_s, "window_s": t1 - t0, "rounds": log_rounds,
            "n_warm": n_warm, "kept": kept, "attempted": events,
            "failed": 0, "events": events,
            "events_per_s": events / (t1 - t0), "host_step_s": host_step,
            "width": B,
            "valid_rows": events,
            "dispatched_rows": n_window * _rows_per_round(mgr) * B}


def _rows_per_round(mgr) -> int:
    return sum(v["capacity"] for v in mgr.describe().values())


# ---------------------------------------------------------------------------
# open loop: a seeded schedule over the JSON-lines transport
# ---------------------------------------------------------------------------


def run_online(mgr, tids, graph, cfg, traffic, seed, seconds, tracer,
               compiles, log, t_process, child):
    """Events are sent by a child process that never imports JAX, on a
    schedule drawn from the seed, to the server's JSON-lines transport.
    Each event's latency runs from the time it was due to the time the
    round that applied it was ready on the device, which a waiter thread
    stamps without holding up the serving loop."""
    import jax
    from repro.serving.frontend import FrontendConfig, ServingFrontend

    fcfg = FrontendConfig(max_wait_s=traffic["deadline_ms"] / 1e3,
                          max_rows=traffic["max_rows"],
                          queue_rows=traffic["queue_rows"],
                          pad_quantum=traffic["pad_quantum"])
    fe = ServingFrontend(mgr, fcfg, record_rounds=True)
    done = {}                          # round index -> device-ready time
    waitq: queue.Queue = queue.Queue()
    kept = {}
    step = mgr.guarded_step

    def recording_step(batches):
        with tracer.span("bench.step"):
            outs = step(batches)
        r = len(fe.round_log) - 1       # the pump logs a round, then steps
        if _sampled(r, seed):
            kept[r] = outs
        waitq.put((r, outs))
        return outs

    def waiter():
        while True:
            item = waitq.get()
            if item is None:
                return
            r, outs = item
            jax.block_until_ready(outs)
            done[r] = time.monotonic()

    warm = int(traffic["warmup_events_per_tenant"])
    mgr.guarded_step = recording_step
    wthread = threading.Thread(target=waiter, daemon=True)
    wthread.start()
    old_switch = sys.getswitchinterval()
    sys.setswitchinterval(5e-4)        # the waiter's stamp waits less
    try:
        replays = [stream.TenantReplay(graph, s)
                   for s in stream.tenant_starts(graph, len(tids))]
        for _ in range(int(traffic["warmup_rounds"])):
            for tid, rp in zip(tids, replays):
                src, dst, eid, ts = rp.take(warm)
                for j in range(warm):
                    fe.submit(tid, src[j], dst[j], eid[j], ts[j])
            fe.pump(force=True)
        mgr.sync()
        _wait_for(lambda: len(done) == len(fe.round_log), 60)
        n_warm = len(fe.round_log)
        n_metrics = len(mgr.metrics)
        fe.event_latencies.reset()
        sched = stream.schedule(traffic, replays, seconds, seed)
        result = asyncio.run(_serve_window(fe, mgr, done, child, seconds,
                                           tracer, compiles))
        child.wait(timeout=60)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        waitq.put(None)
        wthread.join(timeout=60)
        sys.setswitchinterval(old_switch)
        mgr.guarded_step = step

    window = fe.round_log[n_warm:]
    lat, failed = _latencies(window, done, n_warm, sched,
                             result["rejected"], result["t_open"], tids)
    valid = sum(int(np.sum(b.valid)) for rnd in window for b in rnd.values())
    width = traffic["pad_quantum"]
    log(f"window: {len(sched.due)} events due in {seconds} s at "
        f"{traffic['rate_eps']} events/s; {len(window)} rounds; "
        f"{len(result['rejected'])} refused; generator lateness p99 "
        f"{result['lateness_p99_s']} s, max {result['lateness_max_s']} s")
    lat_ms = np.asarray(lat, np.float64) * 1e3
    q = fe.event_latencies.quantile(0.99)
    return {"setup_s": result["t_open"] - t_process,
            "window_s": result["window_s"],
            "rounds": [{t: tuple(b)[:5] for t, b in rnd.items()}
                       for rnd in fe.round_log],
            "n_warm": n_warm, "kept": kept, "attempted": len(sched.due),
            "width": width,
            "failed": failed, "events": len(lat),
            "p50_latency_ms": (float(np.percentile(lat_ms, 50))
                               if len(lat) else None),
            "p99_latency_ms": (float(np.percentile(lat_ms, 99))
                               if len(lat) else None),
            "lateness_p99_s": result["lateness_p99_s"],
            "ingest_to_dispatch_p99_s": q,
            "host_step_s": [m["latency_s"] for m in mgr.metrics[n_metrics:]],
            "valid_rows": valid,
            "dispatched_rows": len(window) * _rows_per_round(mgr) * width}


def _wait_for(cond, timeout):
    end = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > end:
            raise TimeoutError("the device did not finish the rounds")
        time.sleep(0.0005)


def _latencies(rounds, done, n_warm, sched, rejected, t_open, tids):
    """Each applied event's latency, from its due time to the time the
    round that applied it was ready on the device; and how many events
    were refused or never applied. A tenant's accepted events are applied
    in the order sent, which each round's edge ids must show."""
    refused = np.zeros(len(sched.due), bool)
    refused[np.asarray(rejected, np.int64)] = True
    lat, failed = [], int(refused.sum())
    for i, tid in enumerate(tids):
        mine = np.nonzero((sched.tenant == i) & ~refused)[0]
        pos = 0
        for r, rnd in enumerate(rounds):
            if tid not in rnd:
                continue
            n = int(np.sum(rnd[tid].valid))
            ev = mine[pos:pos + n]
            if not np.array_equal(np.asarray(rnd[tid].eid)[:n],
                                  sched.eid[ev]):
                raise RuntimeError(f"tenant {tid}: round {r + n_warm} did "
                                   "not apply the events sent, in order")
            lat.extend(done[r + n_warm] - (t_open + sched.due[ev]))
            pos += n
        failed += len(mine) - pos
    return lat, failed


def start_loadgen(cfg, traffic, seed, seconds):
    """The load generator, started first: it builds its schedule while
    the server sets up, prints ``ready`` and waits for the port."""
    skip = (int(traffic["warmup_events_per_tenant"])
            * int(traffic["warmup_rounds"]))
    n_tenants = len(tenants(cfg))
    graph_shape = cfg["graph"]
    return subprocess.Popen(
        [sys.executable, os.path.join(HERE, "loadgen.py"),
         "--graph", json.dumps(graph_shape), "--traffic", json.dumps(traffic),
         "--seed", str(seed), "--seconds", str(seconds),
         "--tenants", str(n_tenants), "--skip", str(skip)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE)


async def _serve_window(fe, mgr, done, child, seconds, tracer, compiles):
    from repro.serving.frontend import serve_jsonl
    loop = asyncio.get_running_loop()
    await fe.start()
    server = await serve_jsonl(fe, "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    try:
        line = await loop.run_in_executor(None, child.stdout.readline)
        if line.strip() != b"ready":
            raise RuntimeError(f"the load generator did not start: {line!r}")
        tracer.start()
        compiles.armed = True
        with tracer.span("bench.window"):
            t_open = time.monotonic() + 0.3
            child.stdin.write(f"{port} {t_open!r}\n".encode())
            child.stdin.flush()
            line = await asyncio.wait_for(
                loop.run_in_executor(None, child.stdout.readline),
                timeout=seconds + 120)
            if not line:
                raise RuntimeError("the load generator ended early")
            report = json.loads(line)
            with tracer.span("bench.drain"):
                await fe.stop()
                mgr.sync()
                while len(done) < len(fe.round_log):
                    await asyncio.sleep(0.0005)
            t_close = time.monotonic()
        compiles.armed = False
        tracer.stop()
    finally:
        server.close()
        await server.wait_closed()
    return {"t_open": t_open, "window_s": t_close - t_open,
            "rejected": report["rejected"],
            "lateness_p99_s": report["lateness_p99_s"],
            "lateness_max_s": report["lateness_max_s"]}


# ---------------------------------------------------------------------------
# the check: what the window produced against the plain reference
# ---------------------------------------------------------------------------


def served_outputs(mgr, tids, kept):
    """Host copies of what the timed path produced: every tenant's final
    memory table and the kept rounds' embeddings (sources, destinations)."""
    mem = {tid: np.asarray(mgr.state_of(tid).memory) for tid in tids}
    emb = {r: {tid: np.concatenate([np.asarray(o.emb_src),
                                    np.asarray(o.emb_dst)])
               for tid, o in outs.items()} for r, outs in kept.items()}
    return mem, emb


def replay(cfg, graph, weights, rounds, tids, want, precision, log=None):
    """The plain reference over every round of the run, tenant by tenant
    (vmapped over the tenants of a lane). Returns the final memory tables,
    ``{tid: array}``, and for each round in ``want`` each tenant's
    embeddings and selection margins, ``{r: {tid: (emb, margin)}}``."""
    import jax
    import jax.numpy as jnp
    import reference

    w = cfg["widths"]
    lanes = dict(tenants(cfg))
    groups = {}
    for tid in tids:
        groups.setdefault(lane_key(lanes[tid]), []).append(tid)
    ef = jnp.asarray(graph.edge_feats)
    mem_out, emb_out = {}, {r: {} for r in want}
    for key, gt in groups.items():
        lane = ref_lane(lanes[gt[0]], w)
        p = weights[lanes[gt[0]]["params"]]

        def one(p, st, ef, b, _lane=lane):
            return reference.step(p, _lane, st, ef, b, precision)

        def margin(p, st, b, _lane=lane):
            return reference.selection_margin(p, _lane, st, b)

        fn = jax.jit(jax.vmap(one, in_axes=(None, 0, None, 0)),
                     donate_argnums=(1,))
        mfn = jax.jit(jax.vmap(margin, in_axes=(None, 0, 0)))
        st = jax.vmap(lambda _: reference.init_state(
            graph.shape.n_nodes, w, graph.shape.f_edge))(
                jnp.arange(len(gt)))
        if log is not None:
            log(f"reference ({precision}): lane {key[0]} "
                f"{key[1]}, {len(gt)} tenants, {len(rounds)} rounds")
        for r, rnd in enumerate(rounds):
            present = [t for t in gt if t in rnd]
            if not present:
                continue
            width = max(len(rnd[t][0]) for t in present)
            cols = [np.zeros((len(gt), width), d) for d in
                    (np.int32, np.int32, np.int32, np.float32, np.bool_)]
            for i, t in enumerate(gt):
                if t in rnd:
                    for c, x in zip(cols, rnd[t][:5]):
                        c[i, :len(x)] = x
            batch = tuple(jnp.asarray(c) for c in cols)
            if r in want:
                m = mfn(p, st, batch)
            st, h = fn(p, st, ef, batch)
            if log is not None and r < 3:
                jax.block_until_ready(h)
                log(f"reference round {r} done")
            if r in want:
                h, m = np.asarray(h, np.float32), np.asarray(m)
                for i, t in enumerate(gt):
                    if t in rnd:
                        n = len(rnd[t][0])
                        emb_out[r][t] = (
                            np.concatenate([h[i, :n], h[i, width:width + n]]),
                            np.concatenate([m[i, :n], m[i, width:width + n]]))
        mem = np.asarray(st["memory"], np.float32)
        for i, t in enumerate(gt):
            mem_out[t] = mem[i]
    return mem_out, emb_out


def compare(cfg, tids, rounds, got_mem, got_emb, ref_mem, ref_emb) -> dict:
    """The numbers compared, per lane: ``memory_err`` (largest absolute
    error over every tenant's final memory table, values in (-1, 1)) and
    ``emb_err`` (largest absolute error over the kept rounds' embeddings
    of valid rows, over the largest reference magnitude); rows whose
    neighbour selection the served precision cannot decide (a reference
    margin <= 0) are left out of ``emb_err`` and counted."""
    lanes = dict(tenants(cfg))
    out = {}
    names = []
    for lane in cfg["lanes"]:
        if lane["name"] not in names:
            names.append(lane["name"])
    for name in names:
        gt = [t for t in tids if lanes[t]["name"] == name]
        mem_err = max(float(np.max(np.abs(got_mem[t] - ref_mem[t])))
                      for t in gt)
        d2 = sum(float(np.sum((got_mem[t] - ref_mem[t]) ** 2)) for t in gt)
        r2 = sum(float(np.sum(ref_mem[t] ** 2)) for t in gt)
        errs, scale, rows, left_out, e2, s2 = [0.0], 1e-30, 0, 0, 0.0, 0.0
        for r, per in got_emb.items():
            for t in gt:
                if t not in per:
                    continue
                want, margin = ref_emb[r][t]
                n = len(rounds[r][t][0])
                ok = np.concatenate([rounds[r][t][4], rounds[r][t][4]])[:2 * n]
                decided = ok & (margin > 0)
                rows += int(ok.sum())
                left_out += int((ok & ~decided).sum())
                d = np.abs(per[t] - want)[decided]
                if d.size:
                    errs.append(float(d.max()))
                    e2 += float(np.sum(d ** 2))
                    s2 += float(np.sum(want[decided] ** 2))
                scale = max(scale, float(np.max(np.abs(want[ok]),
                                                initial=0.0)))
        out[name] = {"memory_err": mem_err,
                     "memory_rms": (d2 / r2) ** 0.5 if r2 else 0.0,
                     "emb_err": max(errs) / scale,
                     "emb_rms": (e2 / s2) ** 0.5 if s2 else 0.0,
                     "rows": rows, "left_out": left_out}
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

FUSED_KERNEL = "fused_step_pallas"   # the fused step kernel's op name


def run_cell(spec, cell_name, seed, seconds, trace, t_process, log,
             cfg=None, fault=None, control=False, trace_dir=None,
             traffic=None):
    """One run of a cell on whatever backend JAX has. ``cfg`` and
    ``traffic`` replace the cell's configuration and traffic mix (the
    tests' small sizes, the rate sweep that finds the knee); ``fault(mgr)``
    breaks the timed path after it is built; ``control`` also replays the
    run through the reference at ``high`` precision (the control) and
    checks that in the program's place. The program is built, warmed up
    and served at the configuration's ``matmul_precision``. Returns the
    run's record."""
    import jax
    from repro.kernels import ops

    cell, file_cfg, file_traffic = resolve(spec, cell_name)
    cfg = file_cfg if cfg is None else cfg
    traffic = file_traffic if traffic is None else traffic
    loop = traffic["loop"]
    child = None
    if loop == "open":
        child = start_loadgen(cfg, traffic, seed, seconds)
    try:
        with jax.default_matmul_precision(cfg["matmul_precision"]):
            res, mgr, weights, tids, graph, compiles = _measure(
                cfg, cell, traffic, seed, seconds, trace, trace_dir,
                t_process, log, fault, child)
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
    compiles.close()
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:cell["chips"]])
    log(f"compiles in the window: {compiles.n} ({compiles.seconds} s); "
        f"kernel launches traced into the round: {ops.launch_count()}; "
        f"compile counters {mgr.compile_counters()}; peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use')}")
    rows = _rows_per_round(mgr)
    got_mem, got_emb = served_outputs(mgr, tids, res.pop("kept"))
    hlo = res.pop("hlo", "")
    del mgr
    gc.collect()
    log("served outputs copied to the host")

    rec = {"cell": cell_name, "loop": loop, "chips": cell["chips"],
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": peak},
           "compiles_in_window": compiles.n, "rows_per_round": rows,
           "window_rounds": len(res["rounds"]) - res["n_warm"], **res}
    rec["counts"] = _counts(cfg, tids, res, graph.shape.f_edge)
    if dev.platform != "cpu":
        rec["peaks"] = peaks.peaks(dev.device_kind)
    if trace:
        tr = trace_reduce.reduce(
            trace_reduce.find(trace_dir),
            kernels={"fused": trace_reduce.kernel_instructions(
                hlo, FUSED_KERNEL)})
        tr["fused_s"] = tr["kernels"]["fused"]
        labels = trace_reduce.op_labels(hlo)
        tr["ops"] = {(f"{n} {labels[n]}" if n in labels else n): v
                     for n, v in tr["ops"].items()}
        rec["trace"] = tr
        rec["device"]["busy_s"] = tr["busy_s"]
        rec["device"]["window_s"] = tr["window_s"]
        if "peaks" in rec and rec["counts"]["fused_rows"]:
            rec["fused_bound"] = fused_bound(rec["counts"], rec["peaks"])
            log(f"fused kernel: {tr['fused_s']} s on the device, bound by "
                f"{rec['fused_bound']}")

    t = time.monotonic()
    ref_mem, ref_emb = replay(cfg, graph, weights, res["rounds"], tids,
                              set(got_emb), "highest", log)
    rec["errors"] = compare(cfg, tids, res["rounds"], got_mem, got_emb,
                            ref_mem, ref_emb)
    rec["reference_s"] = time.monotonic() - t
    log(f"reference replay done in {rec['reference_s']} s")
    if control:
        ctl_mem, ctl_emb = replay(cfg, graph, weights, res["rounds"], tids,
                                  set(got_emb), "high", log)
        ctl_emb = {r: {t: e for t, (e, _m) in per.items()}
                   for r, per in ctl_emb.items()}
        rec["control_errors"] = compare(cfg, tids, res["rounds"], ctl_mem,
                                        ctl_emb, ref_mem, ref_emb)
        rec["control_checks"] = checks(rec["control_errors"], cfg["limits"])
        rec["control_correct"] = passes(rec["control_checks"])
    rec["limits"] = cfg["limits"]
    rec["checks"] = checks(rec["errors"], cfg["limits"])
    rec["correct"] = passes(rec["checks"])
    return rec


def checks(errors: dict, limits: dict) -> dict:
    """Each number compared, per lane, beside its limit."""
    return {f"{what} {lane}": {"value": errs[what], "limit": limits[what]}
            for lane, errs in errors.items() for what in limits}


def passes(checked: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checked.values())


def _measure(cfg, cell, traffic, seed, seconds, trace, trace_dir, t_process,
             log, fault, child):
    """Build the cell, warm it up and measure its window."""
    from repro.kernels import ops
    compiles = CompileCounter()
    tracer = Tracer(trace, trace_dir)
    t = time.monotonic()
    graph = stream.generate(stream.GraphShape(**cfg["graph"]), seed)
    log(f"graph: {graph.shape.n_nodes} vertices, {graph.n_edges} edges, "
        f"{graph.shape.f_edge}-d edge features, made in "
        f"{time.monotonic() - t} s")
    loop = traffic["loop"]
    mgr, weights, tids = build(cfg, seed, graph, log)
    if fault is not None:
        fault(mgr)
    ops.reset_launch_count()
    if loop == "closed":
        res = run_backlog(mgr, tids, graph, cfg, traffic, seed, seconds,
                          tracer, compiles, log, t_process)
    else:
        res = run_online(mgr, tids, graph, cfg, traffic, seed, seconds,
                         tracer, compiles, log, t_process, child)
    if trace:
        # the program the window ran, compiled again from the cache: its
        # instruction names are those of the trace's device operations
        res["hlo"] = mgr.lower_round(res["width"]).compile().as_text()
    return res, mgr, weights, tids, graph, compiles


def fused_bound(c: dict, p: dict) -> str:
    """Which of the chip's peaks bounds the fused kernel's required work:
    "flops" or "bytes"."""
    return ("flops" if c["fused_flops"] / p["bf16_flops_per_s"]
            >= c["fused_bytes"] / p["hbm_bytes_per_s"] else "bytes")


def _counts(cfg, tids, res, f_edge) -> dict:
    """Required operations and bytes of the events applied in the window."""
    w = cfg["widths"]
    lanes = dict(tenants(cfg))
    per_tid = {t: 0 for t in tids}
    for rnd in res["rounds"][res["n_warm"]:]:
        for t, b in rnd.items():
            per_tid[t] += int(np.sum(b[4]))
    step_flops = fused_flops = fused_bytes = fused_rows = 0
    for t, n in per_tid.items():
        lane = lanes[t]
        k = lane.get("k", w["m_r"])
        step_flops += n * counts.event_flops(lane["params"], k, w, f_edge)
        if lane["tier"] == "fused":
            fused_rows += 2 * n
            fused_flops += 2 * n * counts.fused_row_flops(k, w, f_edge)
            fused_bytes += 2 * n * counts.fused_row_bytes(k, w, f_edge)
    return {"step_flops": step_flops, "fused_rows": fused_rows,
            "fused_flops": fused_flops, "fused_bytes": fused_bytes}
