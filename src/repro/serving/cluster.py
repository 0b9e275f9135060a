"""Sharded tenant fabric: the multi-tenant session on a device mesh.

``serving/session.py`` stacks every same-variant tenant's VertexState and
advances the cohort in one vmapped launch — the software analogue of the
paper's batched datapath. This module is the next scaling layer: place
those stacked ``(tenant, V, ...)`` tables and the padded batch inputs on a
``jax.sharding.Mesh`` so the fleet spreads over devices, the way the
accelerator spreads its Graph Storage over BRAM banks.

  * ``ShardedSessionManager`` — drop-in SessionManager whose cohorts pad
    their stacked tables to a multiple of the mesh ``tenant`` axis (pad
    slots are idle-masked rows, a bitwise no-op) and pin every launch
    operand with the PartitionSpec rules in ``distributed/tgn_sharding.py``:
    state/batches row-sharded over ``tenant`` (optionally ``vertex`` for
    the V dim), params and feature stores replicated. The committing
    launch donates the old state buffers, so resident tables are updated
    in place. Because the vmapped step has no cross-tenant reduction,
    per-tenant trajectories are BITWISE-identical to the unsharded
    SessionManager (tests/test_cluster.py pins this on a forced 8-device
    host mesh).

  * snapshot / restore / migration — built on ``distributed/checkpoint.py``
    (atomic tmp-dir+rename commit, per-leaf crc32, versioned steps): a
    tenant's VertexState plus its variant/config metadata is saved under
    ``<root>/<tenant>/step_XXXXXXXX/`` and restores into ANY manager whose
    shared parameter axes match — a different cohort, a different mesh
    shape, or the unsharded session (the elastic path: checkpoints hold
    full logical arrays, placement is recomputed by the target).

::

    mgr = ShardedSessionManager(params, edge_feats, model=cfg,
                                mesh="tenant=4,vertex=2")
    a = mgr.add_tenant()
    mgr.step({a: batch})
    snapshot_tenant(mgr, a, "/ckpt/fleet", step=rounds)
    # ... later / elsewhere, any mesh shape:
    b = restore_tenant(other_mgr, "/ckpt/fleet", a)
"""
from __future__ import annotations

import dataclasses
import json
import os
import warnings

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from repro.core import mailbox, pipeline as pl, tgn
from repro.distributed import checkpoint as ckpt
from repro.distributed import tgn_sharding as tsh
from repro.serving.session import DEFAULT_PARAMS, SessionManager, _Cohort


class _ShardedCohort(_Cohort):
    """A cohort whose stacked tables live sharded on the fabric mesh."""

    def __init__(self, cfg: tgn.TGNConfig, use_kernels: bool, params: dict,
                 mesh: Mesh, reserve=None, param_set: str = DEFAULT_PARAMS):
        self.mesh = mesh
        super().__init__(cfg, use_kernels, params, reserve=reserve,
                         param_set=param_set)

    def _build_launches(self) -> None:
        super()._build_launches()        # keeps the unsharded _vstep1 peek
        like = jax.eval_shape(self._init_row)
        self.state_shardings = tsh.make_shardings(
            self.mesh, tsh.state_specs(self.mesh, like))
        rep = tsh.replicated(self.mesh)
        batch_sh = tuple(NamedSharding(self.mesh, s)
                         for s in tsh.batch_specs(self.mesh))
        # the coalesced whole-round launch reuses these per-cohort specs
        self.out_shardings = tsh.make_shardings(
            self.mesh, tsh.out_specs(self.mesh, like))
        # node_feats may be None: leave its placement unspecified
        in_sh = (rep, self.state_shardings, batch_sh, rep, None)
        tmap = tsh.tenant_map(self.mesh)
        self._vstep = self.pipeline.batched_step(
            self.aux, in_shardings=in_sh, out_shardings=self.out_shardings,
            tenant_map=tmap)
        self._vstep_commit = self.pipeline.batched_step(
            self.aux, donate_state=True, in_shardings=in_sh,
            out_shardings=self.out_shardings, tenant_map=tmap)

    def _target_capacity(self, n: int) -> int:
        """Mesh-aligned capacity: the reserve ladder (when enabled) picks
        the class, then the mesh rounds it up to a tenant-axis multiple."""
        return tsh.tenant_capacity(super()._target_capacity(n), self.mesh)

    def _place(self, state):
        """Place every leaf with its PartitionSpec."""
        return jax.device_put(state, self.state_shardings)

    def launch(self, stacked_batch, edge_feats, node_feats,
               commit: bool = False) -> tgn.BatchOut:
        fn = self._vstep_commit if commit else self._vstep
        return fn(self.params, self.state, stacked_batch, edge_feats,
                  node_feats)


class ShardedSessionManager(SessionManager):
    """SessionManager on a device mesh: same API, same trajectories.

    ``mesh`` is a ``jax.sharding.Mesh`` or a spec string for
    ``tgn_sharding.make_tenant_mesh`` (``"8"``, ``"tenant=4,vertex=2"``,
    ``None`` = every device on the tenant axis). Shared operands (params,
    edge/node feature stores) are replicated across the mesh once at
    construction; each cohort's stacked state and batch inputs shard over
    the ``tenant`` axis. Everything else — tenant lifecycle, idle masking,
    chronological LWW commits, metrics — is inherited unchanged.
    """

    def __init__(self, params: dict, edge_feats, node_feats=None, *,
                 mesh: Mesh | str | int | None = None, **kw):
        if not isinstance(mesh, Mesh):
            mesh = tsh.make_tenant_mesh(mesh)
        self.mesh = mesh
        # the ParamStore places every registered set via _place_params, so
        # the default set (and any later register_params) replicate here
        super().__init__(params, edge_feats, node_feats, **kw)
        rep = tsh.replicated(mesh)
        self.edge_feats = jax.device_put(self.edge_feats, rep)
        if self.node_feats is not None:
            self.node_feats = jax.device_put(self.node_feats, rep)

    def _place_params(self, params: dict) -> dict:
        """Replicate a registered parameter set across the fabric mesh."""
        return jax.device_put(params, tsh.replicated(self.mesh))

    def _make_cohort(self, cfg: tgn.TGNConfig, use_kernels,
                     param_set: str = DEFAULT_PARAMS) -> _ShardedCohort:
        return _ShardedCohort(cfg, use_kernels,
                              self.param_store.get(param_set), self.mesh,
                              reserve=self.reserve, param_set=param_set)

    def _batch_shardings(self) -> tuple:
        return tuple(NamedSharding(self.mesh, s)
                     for s in tsh.batch_specs(self.mesh))

    def _make_coalesced(self) -> pl.CoalescedRound:
        """The fused whole-round launch with every operand's mesh placement
        pinned: per-cohort states keep their cohort's PartitionSpecs (and
        are DONATED — resident tables update in place, like the per-cohort
        commit launch), the super-batch row-shards over the tenant axis
        (each segment's row count is a capacity, i.e. a multiple of the
        axis), and the in-launch edge count replicates."""
        cohorts = list(self._cohorts.values())
        rep = tsh.replicated(self.mesh)
        # position 0 is the per-lane params TUPLE; a single replicated
        # sharding is a valid pytree prefix, broadcasting to every set
        in_sh = (rep, tuple(c.state_shardings for c in cohorts),
                 self._batch_shardings(), rep, None)
        out_sh = (tuple(c.out_shardings for c in cohorts), rep)
        return pl.CoalescedRound(
            [(c.pipeline, c.aux, c.capacity) for c in cohorts],
            donate_state=True, in_shardings=in_sh, out_shardings=out_sh,
            tenant_map=tsh.tenant_map(self.mesh), obs=self.obs)

    def _make_stager(self, rows: int, width: int):
        from repro.serving.session import _HostStager
        return _HostStager(rows, width, shardings=self._batch_shardings())

    def set_state(self, tid: str, st: mailbox.VertexState) -> None:
        super().set_state(tid, st)
        cohort = self.cohort_of(tid)
        cohort.state = jax.device_put(cohort.state, cohort.state_shardings)

    def describe(self) -> dict:
        return {**super().describe(), "mesh": dict(self.mesh.shape)}


# ---------------------------------------------------------------------------
# tenant snapshot / restore / migration (works on ANY SessionManager)
# ---------------------------------------------------------------------------


def _capture_tenant(mgr: SessionManager, tid: str,
                    extra_meta: dict | None = None) -> tuple[dict, dict]:
    """Grab a consistent (state pytree, manifest meta) pair for ``tid`` on
    the serving thread — device arrays are immutable, so the pair stays
    valid while a background writer gathers and persists it."""
    cohort = mgr.cohort_of(tid)
    st = mgr.state_of(tid)
    meta = {"tenant": tid,
            "variant": pl.variant_name(cohort.cfg),
            "config": dataclasses.asdict(cohort.cfg),
            # the TENANT's resolved kernel tier, not the session default:
            # lanes pick tiers independently (add_tenant(use_kernels=...))
            # and a restore must resume on the same numerics
            "use_kernels": cohort.tier,
            # the parameter set the tenant was serving on + its content
            # digest: a restore must resume on the SAME weights (a
            # trajectory is meaningless under different parameters), so
            # restore_tenant re-binds by name and verifies the digest
            "param_set": cohort.param_set,
            "params_digest": mgr.param_store.digest(cohort.param_set)}
    if extra_meta:
        meta.update(extra_meta)
    return st._asdict(), meta


def snapshot_tenant(mgr: SessionManager, tid: str, root: str, *,
                    step: int = 0, keep: int = 3,
                    extra_meta: dict | None = None,
                    keep_floor: int | None = None) -> str:
    """Atomically snapshot one tenant's VertexState + serving metadata.

    Layout: ``<root>/<tid>/step_XXXXXXXX/`` via ``checkpoint.save`` (tmp
    dir + rename, per-leaf crc32, last ``keep`` steps retained). ``step``
    is the caller's stream position (e.g. rounds served) so successive
    snapshots version the tenant's trajectory. The manifest meta carries
    the resolved variant and full TGNConfig, which ``restore_tenant``
    validates against the target session. With an armed journal the
    caller records the replay cursor via ``extra_meta={"journal":
    journal.cursor(tid)}`` and pins the WAL's anchor step with
    ``keep_floor`` (``checkpoint.save(floor=...)``).
    """
    tree, meta = _capture_tenant(mgr, tid, extra_meta)
    return ckpt.save(os.path.join(root, tid), step, tree, meta=meta,
                     keep=keep, floor=keep_floor)


class TenantSnapshotWriter:
    """Bounded per-tenant background snapshot writer: serving rounds never
    stall on snapshot IO.

    ``submit`` captures the tenant's state on the calling thread (device
    array references + manifest meta — cheap, no host gather) and hands
    the D2H gather plus the atomic ``checkpoint.save`` commit to a worker
    thread. At most ONE snapshot per tenant is in flight: while a
    tenant's previous write is still running, new submissions for it are
    skipped (counted in ``skipped``) — the periodic cadence is
    best-effort, durability comes from the final ``wait()`` + sync save
    at exit. The on-disk format and the tmp-dir + rename + crc32 commit
    of ``distributed/checkpoint.py`` are unchanged.

    A failed write attempt is RETRIED on the worker thread with capped
    exponential backoff (``retries`` attempts beyond the first,
    ``backoff_s`` doubling up to ``backoff_cap_s``) before it counts as
    a failure — transient IO errors never cost a snapshot cadence.
    Retries and exhausted failures land in the fleet metrics registry
    (``snapshot.retries`` / ``snapshot.failures``) when ``obs`` is given;
    exhausted failures still surface at the next ``submit``/``wait``.
    When the manager has an armed fault injector, each write attempt
    runs its ``on_snapshot_write`` hook (docs/ROBUSTNESS.md).
    """

    def __init__(self, root: str, *, keep: int = 3, max_workers: int = 2,
                 retries: int = 2, backoff_s: float = 0.05,
                 backoff_cap_s: float = 1.0, obs=None, sleep=None):
        import time
        from concurrent.futures import ThreadPoolExecutor
        self.root = root
        self.keep = keep
        self.retries = int(retries)
        self.backoff_s = float(backoff_s)
        self.backoff_cap_s = float(backoff_cap_s)
        self.obs = obs                  # MetricsRegistry or None
        self._sleep = sleep if sleep is not None else time.sleep
        self.skipped = 0
        self.written = 0
        self._pool = ThreadPoolExecutor(max_workers=max_workers)
        self._inflight: dict[str, object] = {}

    def submit(self, mgr: SessionManager, tid: str, *, step: int = 0,
               extra_meta: dict | None = None,
               keep_floor: int | None = None) -> bool:
        """Queue a snapshot of ``tid`` at ``step``; returns False when the
        tenant's previous snapshot is still in flight (skipped). A
        previous write that FAILED (retries exhausted) re-raises here —
        with its slot cleared first, so the tenant's cadence resumes on
        the next submit instead of re-raising forever."""
        prev = self._inflight.get(tid)
        if prev is not None:
            if not prev.done():
                self.skipped += 1
                return False
            try:
                prev.result()            # surface a failed write loudly
            except Exception:
                del self._inflight[tid]
                raise
        tree, meta = _capture_tenant(mgr, tid, extra_meta)
        faults = getattr(mgr, "_faults", None)

        def work():
            delay = self.backoff_s
            for attempt in range(self.retries + 1):
                try:
                    if faults is not None:
                        faults.on_snapshot_write(tid)
                    return ckpt.save(os.path.join(self.root, tid), step,
                                     tree, meta=meta, keep=self.keep,
                                     floor=keep_floor)
                except Exception:
                    if attempt >= self.retries:
                        if self.obs is not None:
                            self.obs.counter("snapshot.failures").inc()
                        raise
                    if self.obs is not None:
                        self.obs.counter("snapshot.retries").inc()
                    self._sleep(min(delay, self.backoff_cap_s))
                    delay *= 2

        self._inflight[tid] = self._pool.submit(work)
        self.written += 1
        return True

    def join(self, tid: str) -> None:
        """Block until ``tid``'s in-flight write (if any) lands, clearing
        its slot; re-raises its failure. The guard calls this before an
        auto-restore so the newest snapshot is fully committed (or known
        failed) before the fallback walk picks a step."""
        fut = self._inflight.pop(tid, None)
        if fut is not None:
            fut.result()

    def wait(self) -> None:
        """Join EVERY in-flight write, then re-raise the first failure —
        a failed write never leaves later ones unjoined."""
        errors = []
        for tid, fut in list(self._inflight.items()):
            try:
                fut.result()
            except Exception as e:
                errors.append((tid, e))
            del self._inflight[tid]
        if errors:
            tid, err = errors[0]
            raise RuntimeError(
                f"background snapshot of tenant {tid!r} failed "
                f"({len(errors)} failure(s) total)") from err

    def close(self) -> None:
        try:
            self.wait()
        finally:
            self._pool.shutdown(wait=True)


def snapshot_meta(root: str, tid: str, *, step: int | None = None) -> dict:
    """Read a snapshot's manifest meta without loading any array."""
    d = os.path.join(root, tid)
    if step is None:
        step = ckpt.latest_step(d)
        if step is None:
            raise FileNotFoundError(f"no snapshot for tenant {tid!r} under "
                                    f"{root}")
    with open(os.path.join(d, f"step_{step:08d}", "manifest.json")) as f:
        return json.load(f)["meta"]


def list_snapshots(root: str) -> dict:
    """``{tenant id: latest step}`` of every restorable snapshot."""
    if not os.path.isdir(root):
        return {}
    out = {}
    for tid in sorted(os.listdir(root)):
        step = ckpt.latest_step(os.path.join(root, tid))
        if step is not None:
            out[tid] = step
    return out


def restore_tenant(mgr: SessionManager, root: str, tid: str, *,
                   name: str | None = None, step: int | None = None,
                   params: str | None = None, journal=None) -> str:
    """Restore a snapshotted tenant into ``mgr`` and return its id.

    The target may be a different cohort, a different mesh shape, or the
    unsharded session — snapshots hold full logical arrays, the target
    recomputes placement (elastic path). The snapshot's full TGNConfig
    must match the config the target resolves for its variant; mismatch
    raises before any state is touched. Loads are crc-verified by
    ``checkpoint.restore``.

    The tenant resumes on the parameter set the manifest records
    (``param_set``): the target session must have it registered under the
    same name with the SAME content — the recorded ``params_digest`` is
    verified, so a trajectory never silently continues under different
    weights. Pass ``params=<name>`` to REBIND explicitly onto another
    registered set instead (an A/B promotion: the caller owns the
    numerics break, so the digest check is skipped).

    Corrupt-latest fallback (``step=None`` only): a newest step whose
    manifest or payload fails to load/verify is skipped with a warning
    and the restore falls back to the newest PRIOR valid step
    (``checkpoint.restore_valid``) — a torn background write never
    strands a restorable tenant. An explicit ``step=`` stays strict.

    With ``journal=`` (an ``EventJournal``), the restore becomes
    LOSSLESS: after the state lands, every journaled flush past the
    restored manifest's cursor replays through the normal batching
    pipeline, so the tenant resumes bitwise where the original left
    off — not merely at its last snapshot. The cursor is read from the
    manifest of the step ACTUALLY restored (the fallback walk may land
    below the newest), so replay always starts exactly where that
    state's history ends. ``journal.last_replay.pending`` then holds
    accepted-but-never-flushed events for the caller to re-enqueue.
    """
    d = os.path.join(root, tid)
    meta = _meta_with_fallback(root, tid, step)
    want = meta["config"]
    pname = params if params is not None else meta.get("param_set",
                                                       DEFAULT_PARAMS)
    try:
        mgr.param_store.get(pname)
    except ValueError as e:
        raise ValueError(
            f"snapshot {tid!r} is bound to param set {pname!r} which this "
            f"session has not registered — register_params({pname!r}, ...) "
            "with the original weights before restoring, or pass params= "
            f"to rebind explicitly ({e})") from None
    # resume on the tier the tenant was serving with (older manifests
    # recorded the session default — same key, still honored); missing
    # key = let the target session pick its default
    new = mgr.add_tenant(meta["variant"], name=name or tid,
                         reservoir_tau=want.get("reservoir_tau"),
                         use_kernels=meta.get("use_kernels"),
                         params=pname)
    cohort = mgr.cohort_of(new)
    got = dataclasses.asdict(cohort.cfg)
    if got != want:
        mgr.remove_tenant(new)
        diff = sorted(k for k in set(want) | set(got)
                      if want.get(k) != got.get(k))
        raise ValueError(
            f"snapshot {tid!r} was taken with config fields "
            f"{ {k: want.get(k) for k in diff} } but this session resolves "
            f"{ {k: got.get(k) for k in diff} } — shared parameter axes and "
            "table dims must match to continue the trajectory")
    if params is None and meta.get("params_digest") is not None:
        have = mgr.param_store.digest(pname)
        if have != meta["params_digest"]:
            mgr.remove_tenant(new)
            raise ValueError(
                f"snapshot {tid!r} records param set {pname!r} with digest "
                f"{meta['params_digest']} but this session's {pname!r} "
                f"digests {have} — the trajectory would continue under "
                "different weights; register the original parameters, or "
                "pass params= to rebind explicitly")
    tree_like = cohort.pipeline.init_state()._asdict()
    if step is None:
        state, rmeta, _used = ckpt.restore_valid(d, tree_like)
    else:
        state, rmeta = ckpt.restore(d, tree_like, step=step)
    mgr.set_state(new, mailbox.VertexState(**state))
    if journal is not None and rmeta.get("journal") is not None:
        journal.replay(tid, rmeta["journal"], mgr.step, as_tid=new)
    return new


def truncate_journal(journal, root: str, tid: str) -> int | None:
    """Truncate ``tid``'s WAL up to the OLDEST retained snapshot's
    cursor — the GC-coordination contract (docs/ROBUSTNESS.md): every
    snapshot ``checkpoint._gc`` keeps can still anchor a full replay,
    so truncation never outruns what recovery may need. Steps whose
    manifests are corrupt or pre-journal (no cursor) are skipped — no
    bound can be proven, nothing is deleted. Returns the anchor step
    the truncation is bounded by (pass it as the next snapshot's
    ``keep_floor``), or None when no cursor-bearing snapshot exists.
    """
    for s in ckpt.list_steps(os.path.join(root, tid)):
        try:
            meta = snapshot_meta(root, tid, step=s)
        except ckpt.CORRUPTION_ERRORS:
            return None
        cur = meta.get("journal")
        if cur is None:
            return None
        journal.truncate_upto(tid, cur)
        return s
    return None


def _meta_with_fallback(root: str, tid: str, step: int | None) -> dict:
    """Manifest meta for a restore: the requested step's, or (when
    ``step`` is None) the newest step whose manifest PARSES — a corrupt
    manifest is skipped with a warning, mirroring the payload-side walk
    of ``checkpoint.restore_valid``."""
    if step is not None:
        return snapshot_meta(root, tid, step=step)
    d = os.path.join(root, tid)
    steps = ckpt.list_steps(d)
    for s in reversed(steps):
        try:
            return snapshot_meta(root, tid, step=s)
        except ckpt.CORRUPTION_ERRORS as e:
            warnings.warn(
                f"snapshot manifest for tenant {tid!r} step {s} is "
                f"corrupt ({e}); falling back to the newest prior step")
    raise FileNotFoundError(f"no restorable snapshot for tenant {tid!r} "
                            f"under {root}")


def restore_tenant_state(mgr: SessionManager, root: str, tid: str, *,
                         step: int | None = None) -> int:
    """Reload a RESIDENT tenant's VertexState in place from its newest
    valid snapshot — the guard's auto-restore path (serving/guard.py).

    Unlike ``restore_tenant`` (which ADMITS a new tenant), the tenant is
    already attached and keeps its lane slot: only its state rows are
    replaced. The snapshot must fit the lane it reloads into — the
    recorded TGNConfig must equal the cohort's, and the recorded
    ``params_digest`` must match the lane's resident set (the lane's
    kernel TIER may differ: a guard-degraded lane restores the same
    numerics on a lower tier). With ``step=None`` corrupt steps are
    skipped with a warning (``checkpoint.restore_valid``). Returns the
    step restored from.
    """
    cohort = mgr.cohort_of(tid)
    d = os.path.join(root, tid)
    tree_like = cohort.pipeline.init_state()._asdict()
    if step is None:
        state, meta, used = ckpt.restore_valid(d, tree_like)
    else:
        state, meta = ckpt.restore(d, tree_like, step=step)
        used = step
    want = meta.get("config")
    if want is not None and want != dataclasses.asdict(cohort.cfg):
        diff = sorted(k for k in set(want)
                      if want.get(k) != dataclasses.asdict(
                          cohort.cfg).get(k))
        raise ValueError(
            f"snapshot {tid!r} step {used} was taken with config fields "
            f"{ {k: want.get(k) for k in diff} } but the tenant's lane "
            "resolves differently — an in-place restore must land in the "
            "SAME lane config")
    digest = meta.get("params_digest")
    if digest is not None and digest != mgr.param_store.digest(
            cohort.param_set):
        raise ValueError(
            f"snapshot {tid!r} step {used} records params digest "
            f"{digest} but the lane's {cohort.param_set!r} set digests "
            f"{mgr.param_store.digest(cohort.param_set)} — the "
            "trajectory would resume under different weights")
    mgr.set_state(tid, mailbox.VertexState(**state))
    return used


def migrate_tenant(src: SessionManager, tid: str, dst: SessionManager,
                   root: str, *, step: int | None = None,
                   name: str | None = None, keep: int = 3) -> str:
    """Move a live tenant between sessions through a durable snapshot:
    snapshot on ``src``, restore into ``dst`` (any mesh shape), then
    release the source slot. Returns the tenant's id in ``dst``.

    ``step`` defaults to one past the tenant's latest snapshot under
    ``root``, so a migration never writes a step that sorts below (and
    would lose the latest-step race against) its own history."""
    if step is None:
        prev = ckpt.latest_step(os.path.join(root, tid))
        step = 0 if prev is None else prev + 1
    snapshot_tenant(src, tid, root, step=step, keep=keep)
    new = restore_tenant(dst, root, tid, name=name, step=step)
    src.remove_tenant(tid)
    return new
