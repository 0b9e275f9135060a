"""Serving drivers.

``--mode tgn``: stream a synthetic temporal graph through the optimized
StreamingEngine (Pallas kernels, prune-then-fetch, LUT, chronological
commit) and report latency/throughput — the deployment the paper targets.
With ``--tenants N`` (or ``--tenant-variants``) the stream is split across
N concurrent tenants served by the multi-tenant SessionManager: the whole
mixed-cohort round is ONE coalesced compiled launch fed by in-place host
staging (``--per-cohort`` restores the one-launch-per-cohort baseline),
per-tenant states isolated.

``--mesh`` places the fleet on the sharded tenant fabric
(serving/cluster.py): stacked tenant states and batch inputs shard over
the mesh's ``tenant`` (and optional ``vertex``) axis, trajectories
bitwise-identical to the unsharded session. ``--snapshot-dir`` snapshots
every tenant's VertexState (atomic, crc-checked) every
``--snapshot-every`` rounds and at exit; ``--restore`` resumes any tenant
snapshotted there instead of starting it fresh — including onto a
different mesh shape.

``--listen HOST:PORT`` swaps the offline replay for the ONLINE serving
front-end (serving/frontend.py): a newline-delimited-JSON endpoint
accepting per-tenant edge events, micro-batched into coalesced rounds
under a latency deadline, with live tenant attach/detach over the wire
landing in the compiled round without a recompile (serving/admission.py
capacity classes). See docs/SERVING.md for the protocol.

Observability (both tgn paths): ``--slo-ms`` tracks per-tenant SLO burn
against a latency target, ``--metrics-every`` prints unified
metrics-registry snapshots mid-run, and ``--trace-out``/``--trace-every``
export a sampled span trace of the round loop (Chrome/Perfetto JSON or
JSONL) — see docs/OBSERVABILITY.md.

``--mode lm``: batched prefill+decode generation with a reduced-config LM.

Examples:
    PYTHONPATH=src python -m repro.launch.serve --mode tgn --edges 4000
    PYTHONPATH=src python -m repro.launch.serve --mode tgn --tenants 2 \\
        --listen 127.0.0.1:8471 --deadline-ms 5
    PYTHONPATH=src python -m repro.launch.serve --mode tgn --tenants 4
    PYTHONPATH=src python -m repro.launch.serve --mode tgn \\
        --tenant-variants sat+lut+np4,sat+lut+np4+reservoir
    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \\
        python -m repro.launch.serve --mode tgn --tenants 8 --mesh tenant=8 \\
        --snapshot-dir /tmp/fleet --snapshot-every 5
    PYTHONPATH=src python -m repro.launch.serve --mode lm --arch qwen3_8b
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import use_compile_cache


class _SnapshotHooks:
    """--snapshot-dir plumbing: periodic fleet snapshots + --restore.

    Periodic (``--snapshot-every``) saves go through a bounded per-tenant
    background writer (``cluster.TenantSnapshotWriter``): the round loop
    only captures device-array references, the D2H gather and the atomic
    commit run on worker threads, and a tenant whose previous snapshot is
    still being written is skipped that cadence — a snapshot round no
    longer stalls the fleet. The exit save is synchronous (drain the
    writer, then write every tenant once more) so shutdown is durable.
    """

    def __init__(self, mgr, args, journal=None):
        from repro.core import pipeline
        from repro.serving import cluster
        self.cluster = cluster
        self.pipeline = pipeline
        self.mgr = mgr
        self.root = args.snapshot_dir
        self.do_restore = args.restore
        self.available = cluster.list_snapshots(self.root)
        self.base_step = {}          # tid -> step its trajectory resumed at
        self.writer = cluster.TenantSnapshotWriter(self.root)
        #: the fleet's EventJournal or None. Armed, every snapshot
        #: manifest records the tenant's replay cursor, restores replay
        #: the WAL suffix (lossless resume), and the exit save
        #: truncates the WAL against the oldest retained snapshot.
        self.journal = journal
        self.floor = {}              # tid -> WAL anchor step (gc floor)

    def _meta(self, tid):
        if self.journal is None:
            return None
        return {"journal": self.journal.cursor(tid)}

    def restore(self, variant, name):
        """Revive ``name`` from disk if --restore and a snapshot exists
        (returns the tenant id) else None (caller adds it fresh)."""
        if not (self.do_restore and name in self.available):
            return None
        meta = self.cluster.snapshot_meta(self.root, name)
        want = self.pipeline.variant_name(
            self.pipeline.resolve_variant(variant))
        if want != meta["variant"]:
            raise ValueError(
                f"tenant {name!r} was snapshotted as {meta['variant']!r} "
                f"but this run requests {want!r} — a restored trajectory "
                "keeps its policy; drop the conflicting "
                "--variant/--tenant-variants entry or point --snapshot-dir "
                "at a fresh directory")
        tid = self.cluster.restore_tenant(self.mgr, self.root, name,
                                          journal=self.journal)
        base, replayed = self.available[name], 0
        if self.journal is not None \
                and self.journal.last_replay is not None:
            # the WAL replay advanced the trajectory past the snapshot:
            # the resumed stream window starts after the replayed rounds
            replayed = self.journal.last_replay.rounds
            base += replayed
        self.base_step[tid] = base
        print(f"restored tenant {tid!r} ({meta['variant']}) from "
              f"{self.root} step {self.available[name]}"
              + (f" + {replayed} journal round(s)" if replayed else ""))
        return tid

    def save(self, rounds):
        # periodic cadence: overlap snapshot IO with the serving rounds
        # (bounded: one in-flight write per tenant, stragglers skipped).
        # Quarantined tenants are excluded — their state is suspect, and
        # persisting it would poison the very snapshot the guard's
        # auto-restore falls back to.
        for tid in self.mgr.tenants:
            if self.mgr.is_quarantined(tid):
                continue
            self.writer.submit(self.mgr, tid,
                               step=self.base_step.get(tid, 0) + rounds,
                               extra_meta=self._meta(tid),
                               keep_floor=self.floor.get(tid))

    def save_final(self, rounds):
        # steps continue from each restored trajectory's snapshot, so a
        # resumed run's saves never sort below (and lose the latest-step
        # race against) the history they extend. The writer is drained
        # FIRST (no concurrent writes into a tenant dir its gc could
        # tear), but a failed background write must not abort the exit
        # save — that is the moment durability matters most.
        try:
            self.writer.close()
        except Exception as e:
            print(f"snapshot writer: {e}; writing the exit snapshots "
                  "synchronously anyway")
        for tid in self.mgr.tenants:
            self.cluster.snapshot_tenant(
                self.mgr, tid, self.root,
                step=self.base_step.get(tid, 0) + rounds,
                extra_meta=self._meta(tid),
                keep_floor=self.floor.get(tid))
            if self.journal is not None:
                # exit truncation: drop WAL segments no retained
                # snapshot needs; the anchor step pins future GC
                anchor = self.cluster.truncate_journal(
                    self.journal, self.root, tid)
                if anchor is not None:
                    self.floor[tid] = anchor
        if self.writer.skipped:
            print(f"snapshot writer: {self.writer.skipped} periodic "
                  "save(s) skipped while a previous write was in flight")


def _tgn_setup(args):
    """Shared --mode tgn setup: dataset + config + params + features."""
    from repro.core import tgn
    from repro.core.pipeline import variant_config
    from repro.data import temporal_graph as tgd

    g = tgd.DATASETS[args.dataset](n_edges=args.edges)
    cfg = variant_config(
        args.variant,
        n_nodes=g.cfg.n_nodes, n_edges=g.n_edges, f_edge=g.cfg.f_edge,
        f_feat=g.cfg.f_feat, f_mem=args.f_mem, f_time=args.f_mem,
        f_emb=args.f_mem, m_r=10)
    params = tgn.init_params(jax.random.key(0), cfg)
    node_feats = g.node_feats
    edge_feats = (jnp.asarray(g.edge_feats) if g.edge_feats.shape[1] else
                  jnp.zeros((g.n_edges, cfg.f_edge), jnp.float32))
    return g, cfg, params, edge_feats, node_feats


def _tenant_variants(args) -> list:
    return ([v for v in args.tenant_variants.split(",") if v]
            if args.tenant_variants else [args.variant] * args.tenants)


def _tenant_params(args, n: int) -> list:
    """--tenant-params names aligned with the tenant list, padded with
    the session default set (empty entries mean "default" too)."""
    names = ([p.strip() for p in args.tenant_params.split(",")]
             if args.tenant_params else [])
    if len(names) > n:
        raise SystemExit(f"--tenant-params lists {len(names)} sets for "
                         f"{n} tenants")
    names += [""] * (n - len(names))
    return [p or "default" for p in names]


def _ensure_param_sets(mgr, variants, pnames) -> None:
    """Register every named (non-default) set the fleet asks for.

    The CLI has no weight files to load, so a name maps to a
    deterministic name-seeded init for that tenant's variant config —
    the same name always yields the same weights (and so the same
    snapshot digest across runs). A real deployment would register
    trained checkpoints here instead.
    """
    import zlib

    from repro.core import tgn

    for v, pname in zip(variants, pnames):
        if pname == "default" or pname in mgr.param_store:
            continue
        cfg = mgr._tenant_cfg(v, None, pname)
        seed = zlib.crc32(pname.encode())
        mgr.register_params(pname,
                            tgn.init_params(jax.random.key(seed), cfg))
        print(f"registered param set {pname!r} "
              f"(digest {mgr.param_store.digest(pname)}, seed {seed})")


def _make_guard(mgr, args, writer=None, journal=None):
    """--guard: arm the FleetGuard supervisor (serving/guard.py) — NaN
    sentinel + SLO-burn quarantine, snapshot auto-restore with capped
    backoff and a --max-restores eviction ceiling, kernel-tier
    degradation on classified launch failures. Returns the guard (or
    None); once constructed, every round routes through it. With a
    journal, auto-restores replay the WAL suffix (lossless)."""
    if not args.guard:
        return None
    from repro.serving.guard import FleetGuard
    return FleetGuard(mgr, snapshot_root=args.snapshot_dir, writer=writer,
                      max_restores=args.max_restores,
                      quarantine_slo_burn=args.quarantine_slo_burn,
                      journal=journal)


def _make_journal(args):
    """--journal-dir: arm the durable write-ahead event journal
    (serving/journal.py). Every accepted ingest is logged BEFORE it
    enqueues, ``(client_id, seq)`` retries dedup server-side, and
    restores replay the WAL suffix for lossless recovery (see
    docs/ROBUSTNESS.md, "Recovery semantics")."""
    if not args.journal_dir:
        return None
    from repro.serving.journal import EventJournal
    return EventJournal(args.journal_dir,
                        fsync_s=args.journal_fsync_ms / 1e3,
                        dedup_window=args.dedup_window)


def _make_tracer(args):
    """--trace-out: build the sampled round tracer (obs/trace.py)."""
    if not args.trace_out:
        return None
    from repro.obs import RoundTracer
    return RoundTracer(sample_every=args.trace_every)


def _export_trace(tracer, args):
    """Write the collected spans at exit: Chrome/Perfetto trace_event
    JSON by default, span-per-line JSONL when the path ends .jsonl."""
    if tracer is None:
        return
    if args.trace_out.endswith(".jsonl"):
        tracer.write_jsonl(args.trace_out)
    else:
        tracer.write_chrome(args.trace_out)
    print(f"trace: {tracer.summary()} -> {args.trace_out}")


def _print_metrics(obs, tag=""):
    import json
    print(f"metrics{tag}:",
          json.dumps(obs.snapshot(), sort_keys=True, default=float),
          flush=True)


def run_frontend(args):
    """--listen: the online serving front-end (serving/frontend.py).

    Boots a reserve-enabled SessionManager (live admission: attach/detach
    over the wire land in the compiled round without a recompile), wraps
    it in the deadline-batching ServingFrontend, and serves the
    newline-delimited-JSON protocol on the requested address. One request
    dict per line, one response per line — see docs/SERVING.md."""
    import asyncio

    from repro.serving.admission import CapacityLadder
    from repro.serving.frontend import (FrontendConfig, ServingFrontend,
                                        serve_jsonl)
    from repro.serving.session import SessionManager

    _g, cfg, params, edge_feats, node_feats = _tgn_setup(args)
    mgr = SessionManager(params, edge_feats, node_feats, model=cfg,
                         use_kernels=args.kernels, reserve=CapacityLadder())
    variants = _tenant_variants(args)
    pnames = _tenant_params(args, len(variants))
    _ensure_param_sets(mgr, variants, pnames)
    for i, (v, p) in enumerate(zip(variants, pnames)):
        mgr.add_tenant(v, name=f"t{i}", params=p)
    fcfg = FrontendConfig(max_wait_s=args.deadline_ms / 1e3,
                          max_rows=args.max_rows,
                          queue_rows=args.queue_rows,
                          pad_quantum=args.pad_quantum)
    tracer = _make_tracer(args)
    journal = _make_journal(args)
    fe = ServingFrontend(mgr, fcfg, tracer=tracer,
                         slo_ms=args.slo_ms or None,
                         slo_objective=args.slo_objective,
                         journal=journal)
    guard = _make_guard(mgr, args, journal=journal)
    host, _, port = args.listen.partition(":")

    async def serve():
        await fe.start()
        server = await serve_jsonl(fe, host or "127.0.0.1", int(port or 0))
        addr = server.sockets[0].getsockname()
        print(f"serving JSON-lines on {addr[0]}:{addr[1]} "
              f"(deadline {fcfg.max_wait_s * 1e3:.1f}ms, "
              f"max-rows {fcfg.max_rows}, tenants {list(mgr.tenants)})",
              flush=True)
        ticker = None
        if args.metrics_every:
            async def tick():
                # online mode has no round counter to key off, so
                # --metrics-every is SECONDS here (rounds offline)
                while True:
                    await asyncio.sleep(args.metrics_every)
                    _print_metrics(fe.obs)
            ticker = asyncio.create_task(tick())
        try:
            if args.serve_seconds > 0:
                await asyncio.sleep(args.serve_seconds)
            else:
                await asyncio.Event().wait()      # forever; Ctrl-C stops
        finally:
            if ticker is not None:
                ticker.cancel()
            server.close()
            await server.wait_closed()
            await fe.stop()

    try:
        asyncio.run(serve())
    except KeyboardInterrupt:
        pass
    if journal is not None:
        journal.close()             # fsync the tail: exit is durable
    print("frontend stats:", fe.stats())
    if args.slo_ms:
        print("slo:", {tid: mgr.slo.tenant(tid) for tid in mgr.tenants})
    if guard is not None:
        print("guard:", guard.snapshot())
    _export_trace(tracer, args)


def run_tgn(args):
    from repro.data import stream
    from repro.serving.engine import EngineConfig, StreamingEngine
    from repro.serving.session import SessionManager

    g, cfg, params, edge_feats, node_feats = _tgn_setup(args)

    tenant_variants = _tenant_variants(args)
    if args.tenant_variants or args.tenants > 1 or args.mesh is not None \
            or args.snapshot_dir or args.slo_ms or args.trace_out \
            or args.guard or args.journal_dir:
        # multi-tenant: split the stream into one contiguous feed per
        # tenant; same-variant tenants share one vmapped launch per round.
        # (--snapshot-dir forces this path too: snapshots are a session
        # feature, and a 1-tenant session serves bitwise like the engine.
        # Likewise --slo-ms/--trace-out/--guard: SLO burn, round tracing
        # and the FleetGuard supervisor live on the session.)
        coalesce = not args.per_cohort
        if args.mesh is not None:
            from repro.serving.cluster import ShardedSessionManager
            mgr = ShardedSessionManager(params, edge_feats, node_feats,
                                        model=cfg, use_kernels=args.kernels,
                                        mesh=args.mesh, coalesce=coalesce)
        else:
            mgr = SessionManager(params, edge_feats, node_feats, model=cfg,
                                 use_kernels=args.kernels, coalesce=coalesce)
        tracer = _make_tracer(args)
        if tracer is not None:
            mgr.set_tracer(tracer)
        if args.slo_ms:
            mgr.set_slo(args.slo_ms, args.slo_objective)
        journal = _make_journal(args)
        snapshots = (_SnapshotHooks(mgr, args, journal=journal)
                     if args.snapshot_dir else None)
        guard = _make_guard(mgr, args,
                            writer=snapshots.writer if snapshots else None,
                            journal=journal)
        pnames = _tenant_params(args, len(tenant_variants))
        _ensure_param_sets(mgr, tenant_variants, pnames)
        tids = []
        for i, (v, p) in enumerate(zip(tenant_variants, pnames)):
            tid = snapshots.restore(v, f"t{i}") if snapshots else None
            tids.append(tid if tid is not None else
                        mgr.add_tenant(v, name=f"t{i}", params=p))
        print("session cohorts:", {v: i["tenants"]
                                   for v, i in mgr.describe().items()
                                   if isinstance(i, dict)
                                   and "tenants" in i})
        if args.mesh is not None:
            print("fabric mesh:", dict(mgr.mesh.shape))
        span = g.n_edges // len(tids)
        streams = {}
        for i, tid in enumerate(tids):
            lo = i * span
            if snapshots:
                # a restored tenant RESUMES its window where the snapshot
                # left off (one round = one --batch of edges; resuming
                # assumes the same --batch) instead of re-ingesting edges
                # its state already contains; a fully-consumed window
                # leaves the tenant idle.
                lo += min(snapshots.base_step.get(tid, 0) * args.batch,
                          span)
            streams[tid] = stream.fixed_count(
                g, args.batch, window=slice(lo, (i + 1) * span))
        if journal is not None:
            # write-ahead for the offline path: each batch journals
            # (rows + flush marker) as the driver PULLS it — before the
            # round that applies it ever launches
            def journaled(tid, it):
                for b in it:
                    journal.append_batch(tid, b)
                    yield b
            streams = {t: journaled(t, s) for t, s in streams.items()}
        rounds = 0
        for _batches, _outs in mgr.run(streams):
            rounds += 1
            if snapshots and args.snapshot_every and \
                    rounds % args.snapshot_every == 0:
                snapshots.save(rounds)
            if args.metrics_every and rounds % args.metrics_every == 0:
                _print_metrics(mgr.obs, tag=f" (round {rounds})")
        if snapshots:
            snapshots.save_final(rounds)
            steps = {t: snapshots.base_step.get(t, 0) + rounds
                     for t in sorted(mgr.tenants)}
            print(f"snapshots: {steps} -> {args.snapshot_dir}")
        if journal is not None:
            jstats = journal.stats()
            journal.close()         # fsync the tail: exit is durable
            print("journal:", jstats, "->", args.journal_dir)
        print("session summary:", mgr.summary())
        if guard is not None:
            print("guard:", guard.snapshot())
        _export_trace(tracer, args)
        return

    engine = StreamingEngine(EngineConfig(model=cfg,
                                          use_kernels=args.kernels),
                             params, edge_feats, node_feats)
    print("engine stages:", engine.describe())
    if args.window_s:
        batches = stream.time_window(g, args.window_s, args.batch)
    else:
        batches = stream.fixed_count(g, args.batch)
    for _batch, _out in engine.run(batches):
        pass
    print("engine summary:", engine.summary())


def run_lm(args):
    from repro import configs
    from repro.models import lm_common
    from repro.serving import lm_serve

    cfg = configs.get(args.arch).smoke_config()
    params = lm_common.init_params(jax.random.key(0), cfg)
    prompts = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab, size=(args.batch, 8)),
        jnp.int32)
    out = lm_serve.generate(params, cfg, prompts,
                            lm_serve.ServeConfig(
                                max_new_tokens=args.new_tokens,
                                temperature=args.temperature))
    print(f"generated {out['tokens'].shape}; "
          f"prefill {out['prefill_s']*1e3:.1f}ms, "
          f"decode {out['decode_s_per_tok']*1e3:.2f}ms/token")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("tgn", "lm"), default="tgn")
    ap.add_argument("--dataset", default="wikipedia",
                    choices=("wikipedia", "reddit", "gdelt"))
    ap.add_argument("--edges", type=int, default=4000)
    ap.add_argument("--f-mem", type=int, default=32)
    ap.add_argument("--variant", default="sat+lut+np4",
                    help="pipeline-registry variant spec (e.g. teacher, "
                         "+NP(M), sat+lut+np2, sat+lut+np4+reservoir)")
    ap.add_argument("--tenants", type=int, default=1,
                    help="serve N concurrent tenant streams through the "
                         "multi-tenant SessionManager (each gets 1/N of "
                         "the edge stream)")
    ap.add_argument("--tenant-variants", default="",
                    help="comma-separated per-tenant variant specs "
                         "(overrides --tenants; attention+encoder must "
                         "match --variant, sampler/pruning may differ — "
                         "unless the tenant also names its own param set "
                         "via --tenant-params)")
    ap.add_argument("--tenant-params", default="",
                    help="comma-separated per-tenant parameter-set names "
                         "aligned with the tenant list (shorter lists pad "
                         "with the default set). Unknown names are "
                         "registered from a deterministic name-seeded "
                         "init; tenants with different sets serve in "
                         "separate lanes of the SAME coalesced launch")
    ap.add_argument("--kernels", default="staged",
                    choices=("ref", "staged", "fused"),
                    help="kernel tier: jnp references, one Pallas kernel "
                         "per unit, or the fused single-pass step kernel "
                         "(kernels/fused_step.py; SAT+LUT variants — "
                         "others degrade to staged)")
    ap.add_argument("--per-cohort", action="store_true",
                    help="dispatch one compiled launch per cohort per "
                         "round (the pre-coalescing baseline) instead of "
                         "the fused single-launch round")
    ap.add_argument("--mesh", default=None,
                    help="serve on the sharded tenant fabric: a device-"
                         "mesh spec like '8' or 'tenant=4,vertex=2' "
                         "(CPU hosts: set XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=N first)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="snapshot every tenant's VertexState here "
                         "(atomic + crc32, via distributed/checkpoint.py)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="also snapshot every N rounds (0: only at exit)")
    ap.add_argument("--restore", action="store_true",
                    help="resume tenants found in --snapshot-dir instead "
                         "of starting them fresh (any mesh shape)")
    ap.add_argument("--listen", default=None, metavar="HOST:PORT",
                    help="serve the online JSON-lines frontend instead of "
                         "replaying the offline stream (port 0 = "
                         "ephemeral; see docs/SERVING.md for the "
                         "protocol)")
    ap.add_argument("--deadline-ms", type=float, default=10.0,
                    help="frontend flush deadline: a round launches when "
                         "the oldest queued event is this old")
    ap.add_argument("--max-rows", type=int, default=128,
                    help="frontend flush size: a round launches when any "
                         "tenant has this many events queued")
    ap.add_argument("--queue-rows", type=int, default=1024,
                    help="per-tenant ingest bound; beyond it events are "
                         "rejected with retry_after (backpressure)")
    ap.add_argument("--pad-quantum", type=int, default=32,
                    help="pad flushed batches to a multiple of this so "
                         "the compiled round's static widths stay stable "
                         "(0: exact sizes, retraces on new widths)")
    ap.add_argument("--serve-seconds", type=float, default=0.0,
                    help="with --listen: serve this long then exit "
                         "(0: run until interrupted)")
    ap.add_argument("--slo-ms", type=float, default=0.0,
                    help="per-tenant latency SLO target: track burn rate "
                         "against this target (offline: round wall; "
                         "--listen: per-event queue+serve latency). 0 "
                         "disables (see docs/OBSERVABILITY.md)")
    ap.add_argument("--slo-objective", type=float, default=0.99,
                    help="SLO objective quantile, e.g. 0.99 = 'p99 under "
                         "--slo-ms'; burn rate 1.0 means the error budget "
                         "is being consumed exactly on schedule")
    ap.add_argument("--metrics-every", type=int, default=0,
                    help="print a metrics-registry snapshot every N rounds "
                         "(offline) or every N seconds (--listen); 0 "
                         "disables")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export the sampled round trace at exit: Chrome/"
                         "Perfetto trace_event JSON (open in ui.perfetto."
                         "dev), or one-span-per-line JSONL if PATH ends "
                         ".jsonl")
    ap.add_argument("--trace-every", type=int, default=8,
                    help="trace 1 in N rounds (sampled rounds add device "
                         "fences for span accuracy, so keep this >1 to "
                         "preserve async pipelining on the rest)")
    ap.add_argument("--guard", action="store_true",
                    help="arm the FleetGuard supervisor: per-round finite-"
                         "state health checks, tenant quarantine with auto-"
                         "restore (from --snapshot-dir when set), and "
                         "kernel-tier degradation on launch failure (see "
                         "docs/ROBUSTNESS.md)")
    ap.add_argument("--max-restores", type=int, default=3,
                    help="evict a quarantined tenant after this many failed "
                         "restore attempts (requires --guard)")
    ap.add_argument("--quarantine-slo-burn", type=float, default=0.0,
                    help="quarantine a tenant whose SLO burn rate exceeds "
                         "this threshold (requires --guard and --slo-ms; "
                         "0 disables the SLO trigger)")
    ap.add_argument("--journal-dir", default=None,
                    help="write-ahead event journal root: every accepted "
                         "event is durably logged BEFORE it enqueues, "
                         "(client_id, seq) ingest retries dedup server-"
                         "side, and restores replay the journal suffix "
                         "for lossless recovery (docs/ROBUSTNESS.md)")
    ap.add_argument("--journal-fsync-ms", type=float, default=5.0,
                    help="batch journal fsyncs on this interval (0: fsync "
                         "every append — strongest durability, highest "
                         "ingest latency; see benchmarks/"
                         "frontend_latency.py for the cost curve)")
    ap.add_argument("--dedup-window", type=int, default=1024,
                    help="per-client sliding seq window for exactly-once "
                         "ingest; size it above a client's max in-flight "
                         "retry depth")
    ap.add_argument("--batch", type=int, default=200)
    ap.add_argument("--window-s", type=float, default=0.0)
    ap.add_argument("--arch", default="qwen3_8b")
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    use_compile_cache()
    if args.restore and not args.snapshot_dir:
        ap.error("--restore needs --snapshot-dir")
    if args.snapshot_every and not args.snapshot_dir:
        ap.error("--snapshot-every needs --snapshot-dir")
    if args.listen is not None and args.mode != "tgn":
        ap.error("--listen is a --mode tgn feature")
    if (args.slo_ms or args.trace_out or args.metrics_every) \
            and args.mode != "tgn":
        ap.error("--slo-ms/--trace-out/--metrics-every are --mode tgn "
                 "features")
    if args.slo_ms < 0:
        ap.error("--slo-ms must be >= 0")
    if not 0.0 < args.slo_objective < 1.0:
        ap.error("--slo-objective must be in (0, 1)")
    if args.trace_every < 1:
        ap.error("--trace-every must be >= 1")
    if args.metrics_every < 0:
        ap.error("--metrics-every must be >= 0")
    if args.guard and args.mode != "tgn":
        ap.error("--guard is a --mode tgn feature")
    if args.max_restores < 1:
        ap.error("--max-restores must be >= 1")
    if args.quarantine_slo_burn < 0:
        ap.error("--quarantine-slo-burn must be >= 0")
    if args.quarantine_slo_burn and not args.slo_ms:
        ap.error("--quarantine-slo-burn needs --slo-ms")
    if args.journal_dir and args.mode != "tgn":
        ap.error("--journal-dir is a --mode tgn feature")
    if args.journal_fsync_ms < 0:
        ap.error("--journal-fsync-ms must be >= 0")
    if args.dedup_window < 1:
        ap.error("--dedup-window must be >= 1")
    if args.listen is not None:
        run_frontend(args)
    else:
        (run_tgn if args.mode == "tgn" else run_lm)(args)


if __name__ == "__main__":
    main()
