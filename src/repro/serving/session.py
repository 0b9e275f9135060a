"""Multi-tenant streaming sessions: many edge streams, one device launch.

The paper's accelerator serves ONE chronological edge stream. A production
deployment (ROADMAP north star; StreamTGN's framing in PAPERS.md) serves
many concurrent, independent streams — per-customer transaction feeds,
per-region event streams — over a registry of named parameter sets (the
teacher, its distilled students, per-tenant fine-tunes). ``SessionManager``
hosts those streams as *tenants*:

  * every tenant owns an independent ``VertexState`` pytree (its own memory
    table, mailbox, and neighbor ring buffer) and picks its own pipeline
    variant — sampler backends included, e.g. one tenant on
    ``sat+lut+np4`` and another on ``sat+lut+np4+reservoir``;
  * tenants with the SAME variant, kernel tier AND parameter set form a
    *cohort*: their states are stacked along a leading tenant axis and one
    ``jax.jit(jax.vmap(step))`` launch advances the whole cohort — batched
    gathers/scatters over the stacked tables, per-tenant chronological
    last-write-wins commits preserved;
  * named parameter sets (``register_params`` / ``ParamStore``) give each
    lane its OWN device-resident weights — ``add_tenant(..., params=
    "studentB")`` lands a tenant on that set, so a vanilla+cosine teacher
    and its sat+lut students A/B-serve in ONE coalesced launch;
  * tenants that submit no batch in a round are masked (an all-``valid=False``
    batch): the launch still has a fixed shape, and the LWW committer plus
    the OOB-redirected ring-buffer insert make a fully-masked step a bitwise
    no-op on that tenant's state.

Numerics contract (tests/test_session.py): a cohort of N tenants produces
BITWISE-identical per-tenant trajectories to N separate single-tenant
sessions, because every path — ``StreamingEngine`` included, which is now a
single-tenant view of this class — runs through the same vmapped step and
vmapped XLA numerics are invariant to the batch size along the mapped axis.
(The randomized sampler backends keep that guarantee by deriving their draws
from a stateless hash of the batch contents, not from threaded PRNG keys.)

Since the coalesced-round tentpole, a full round is ONE compiled launch
regardless of cohort count (``pipeline.CoalescedRound``: cohorts are
contiguous row segments of a common super-batch, variant stages selected
by the static lane table) and the host side of the round is
allocation-free: batches are written in place into pre-allocated,
double-buffered NumPy ring buffers and shipped with a single
``device_put`` per round, so the H2D transfer of round k+1 overlaps the
compute of round k. ``coalesce=False`` keeps the original one-launch-per-
cohort dispatch as the measured baseline (``benchmarks/multitenant.py``)
— both paths replay bitwise-identically.

Cohorts recompile when their tenant count or padded batch size changes;
steady-state serving (fixed fleet, fixed batch cap) reuses one executable
per cohort (per round, when coalesced).
"""
from __future__ import annotations

import functools
import time
from typing import Iterable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mailbox, pipeline as pl, tgn
from repro.data.stream import EdgeBatch
from repro.kernels import ops as kops
from repro.obs import Histogram, MetricsRegistry, span


def _as_device_tuple(batch) -> tuple:
    """Normalize an EdgeBatch / 5-tuple to on-device (src,dst,eid,ts,valid)."""
    if isinstance(batch, EdgeBatch):
        batch = (batch.src, batch.dst, batch.eid, batch.ts, batch.valid)
    src, dst, eid, ts, valid = batch
    if valid is None:
        valid = jnp.ones(jnp.asarray(src).shape, bool)
    return (jnp.asarray(src), jnp.asarray(dst), jnp.asarray(eid),
            jnp.asarray(ts), jnp.asarray(valid))


def _as_host_tuple(batch) -> tuple:
    """Normalize an EdgeBatch / 5-tuple to HOST (src,dst,eid,ts,valid)
    arrays — the form the in-place ring-buffer stager consumes. Already-
    device arrays are brought back (the engine's pre-staged path); host
    NumPy batches (the streaming common case) pass through without a copy.
    """
    if isinstance(batch, EdgeBatch):
        batch = (batch.src, batch.dst, batch.eid, batch.ts, batch.valid)
    src, dst, eid, ts, valid = (np.asarray(x) if x is not None else None
                                for x in batch)
    if valid is None:
        valid = np.ones(src.shape, bool)
    return src, dst, eid, ts, valid


class _HostStager:
    """Pre-allocated, double-buffered host staging of a round's super-batch.

    The original round path allocated per tenant per round
    (``jnp.asarray`` + ``jnp.pad`` per batch, then a ``jnp.stack`` per
    cohort — each a separate device dispatch). The stager instead owns two
    sets of ``(rows, width)`` NumPy buffers (one per field of the batch
    five-tuple), fills the submitted rows IN PLACE on the host, and ships
    the whole super-batch with a single ``device_put`` per round.

    Double buffering: rounds alternate between the two buffer sets, so the
    (async) H2D transfer of round k can still be draining while round
    k+1's batches are written into the other set — the transfer overlaps
    the in-flight compute. Before a set is reused, its previous transfer
    AND the launch that consumed it are waited on (both two rounds stale,
    not a D2H sync of the current round). The transfer alone is NOT a
    sufficient reuse gate: ``device_put`` on the CPU backend zero-copies
    suitably aligned NumPy buffers, so the "device" array can alias this
    host memory and the round-k executable may still be reading it when
    round k+2 refills the set in place — the caller registers the launch
    outputs via ``note_consumer`` to close that race.

    ``width`` grows sticky to the largest batch seen (growth is a
    relayout: fresh buffers, new launch shape); extra columns and
    unsubmitted rows are ``valid=False`` padding, which the step turns
    into bitwise no-ops.
    """

    DTYPES = (np.int32, np.int32, np.int32, np.float32, np.bool_)

    def __init__(self, rows: int, width: int = 1, shardings=None):
        self.rows = int(rows)
        self.width = max(int(width), 1)
        self.shardings = shardings      # per-field placements (mesh fleets)
        self._alloc()

    def _alloc(self) -> None:
        self._bufs = [tuple(np.zeros((self.rows, self.width), dt)
                            for dt in self.DTYPES) for _ in range(2)]
        # per set: everything that must resolve before the set may be
        # rewritten — the device_put result, joined by the consuming
        # launch's outputs once note_consumer is called
        self._inflight: list[tuple | None] = [None, None]
        self._turn = 0
        self._last = 0

    def ensure_width(self, width: int) -> None:
        """Grow the staged batch width (sticky; a relayout)."""
        if width > self.width:
            self.drain()                 # old buffers may still be read
            self.width = int(width)
            self._alloc()

    def stage(self, row_batches: Mapping[int, tuple]) -> tuple:
        """Fill ``{row: host five-tuple}`` into the next buffer set and
        dispatch ONE ``device_put`` for the whole super-batch. Unlisted
        rows are all-``valid=False`` (idle). Returns the device tuple."""
        turn = self._turn
        self._turn = 1 - turn
        prev = self._inflight[turn]
        if prev is not None:             # reuse gate: transfer + consumer
            with span("session.stage_wait"):
                jax.block_until_ready(prev)
        buf = self._bufs[turn]
        for field in buf:
            field.fill(0)                # deterministic padding rows
        for row, host in row_batches.items():
            b = host[0].shape[0]
            for field, src in zip(buf, host):
                field[row, :b] = src
        dev = (jax.device_put(buf, self.shardings)
               if self.shardings is not None else jax.device_put(buf))
        self._inflight[turn] = dev
        self._last = turn
        return dev

    def note_consumer(self, outputs) -> None:
        """Join ``outputs`` (any pytree of device arrays produced by the
        launch that consumed the last staged set) into that set's reuse
        gate. Without this, a zero-copy-aliased set could be rewritten
        while the (async) consuming executable still reads it — see the
        class docstring. Blocking happens two rounds later, in ``stage``,
        so the never-block round contract is untouched."""
        dev = self._inflight[self._last]
        if dev is not None:
            self._inflight[self._last] = (dev, outputs)

    def drain(self) -> None:
        """Wait for every outstanding transfer + consumer (relayout /
        teardown)."""
        for dev in self._inflight:
            if dev is not None:
                jax.block_until_ready(dev)
        self._inflight = [None, None]


def _pad_dev(dev: tuple, B: int) -> tuple:
    """Pad a device tuple to B rows; padding rows are ``valid=False`` (their
    state writes are dropped, so results on real rows are unchanged)."""
    b = dev[0].shape[0]
    if b == B:
        return dev
    pad = B - b
    return (jnp.pad(dev[0], (0, pad)), jnp.pad(dev[1], (0, pad)),
            jnp.pad(dev[2], (0, pad)), jnp.pad(dev[3], (0, pad)),
            jnp.pad(dev[4], (0, pad)))  # bool pads with False


def _idle_dev(B: int) -> tuple:
    """An all-masked batch: advances a tenant's slot without changing it."""
    zi = jnp.zeros((B,), jnp.int32)
    return (zi, zi, zi, jnp.zeros((B,), jnp.float32), jnp.zeros((B,), bool))


#: the per-tenant leaves of a round's ``BatchOut``: every field but
#: ``state``, which stays in the session (``step`` hands out ``state=None``)
_OUT_LEAVES = tgn.BatchOut._fields[1:]


@jax.jit
def _split_out(leaves: tuple) -> tuple:
    """Every slot's unbatched ``_OUT_LEAVES`` of a cohort's stacked round
    output, as ONE compiled program: ``out[i]`` is slot ``i``'s five
    leaves. The cache key is the stacked shapes alone — fixed by the
    cohort's capacity and round width, as the round program is — so it
    compiles once per layout, whichever tenants submitted (eager per-slot
    indexing would dispatch five programs per tenant per round)."""
    return tuple(tuple(x[i] for x in leaves)
                 for i in range(leaves[0].shape[0]))


@functools.partial(jax.jit, static_argnums=0)
def _trim_out(b: int, leaves: tuple) -> tuple:
    """One slot's ``_OUT_LEAVES`` cut from the cohort's round width ``B``
    back to the tenant's own ``b`` rows: rows ``[0:b]``, and ``[0:b]`` +
    ``[B:B+b]`` of the 2B-row distill views (concat([src rows, dst
    rows])). One compiled program per ``(B, b)``."""
    B = leaves[0].shape[0]
    src, dst, *two = leaves
    return (src[:b], dst[:b],
            *(jnp.concatenate([x[:b], x[B:B + b]]) for x in two))


#: the parameter-set name every tenant serves on unless it names another.
DEFAULT_PARAMS = "default"


def _tree_signature(tree) -> dict:
    """``{leaf path: (shape, dtype)}`` of a pytree — works on real arrays
    and on ``jax.eval_shape`` ShapeDtypeStructs alike."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(kp): (tuple(v.shape), str(v.dtype))
            for kp, v in flat}


@functools.lru_cache(maxsize=64)
def _cfg_param_signature(cfg: tgn.TGNConfig) -> dict:
    """The parameter signature ``cfg``'s step consumes (abstract init —
    no weights are materialized). Cached per config: ``add_tenant``
    validates every named-set binding against this."""
    want = jax.eval_shape(lambda: tgn.init_params(jax.random.key(0), cfg))
    return _tree_signature(want)


class ParamStore:
    """Named, device-resident parameter sets — the registry behind the
    coalesced round's per-lane params dimension.

    One set is registered at construction under ``DEFAULT_PARAMS``; more
    arrive via ``register`` (``SessionManager.register_params``). Sets are
    immutable once registered: re-registering a name with byte-identical
    content is a no-op, with different content an error — a lane's
    resident weights never change out from under its serving tenants
    (swap = register a new name, attach tenants to it, drain the old).
    ``digest`` (crc32 over leaf paths + bytes, ``checkpoint.tree_digest``)
    is the identity snapshot manifests record so a restore can verify it
    resumes on the same weights.

    ``place`` is the device-placement hook (the sharded session replicates
    every set across its mesh); the default leaves arrays where they are.
    """

    def __init__(self, default_params: dict, *, place=None):
        self._place = place if place is not None else (lambda p: p)
        self._sets: dict[str, dict] = {}
        self._digests: dict[str, str] = {}
        self.register(DEFAULT_PARAMS, default_params)

    def register(self, name: str, params: dict) -> dict:
        """Register (and place) a named set; returns the resident pytree."""
        if not isinstance(name, str) or not name:
            raise ValueError("param-set name must be a non-empty string, "
                             f"got {name!r}")
        from repro.distributed.checkpoint import tree_digest
        digest = tree_digest(params)
        if name in self._sets:
            if digest != self._digests[name]:
                raise ValueError(
                    f"param set {name!r} is already registered with "
                    f"different content (digest {self._digests[name]} vs "
                    f"{digest}); registered sets are immutable — register "
                    "the new weights under a new name and attach tenants "
                    "to that")
            return self._sets[name]          # idempotent re-register
        self._sets[name] = self._place(params)
        self._digests[name] = digest
        return self._sets[name]

    def get(self, name: str) -> dict:
        if name not in self._sets:
            raise ValueError(
                f"unknown param set {name!r}; registered: "
                f"{sorted(self._sets)}. Register it first "
                "(SessionManager.register_params(name, params)) — "
                "admission never invents weights")
        return self._sets[name]

    def digest(self, name: str) -> str:
        self.get(name)
        return self._digests[name]

    def names(self) -> tuple:
        return tuple(self._sets)

    def __contains__(self, name) -> bool:
        return name in self._sets

    def check_binding(self, name: str, cfg: tgn.TGNConfig) -> None:
        """Validate that the named set structurally fits ``cfg``'s step —
        pytree structure, leaf shapes and dtypes must match what
        ``tgn.init_params`` would produce for that config (a teacher set
        cannot drive a SAT lane and vice versa). Raises with the exact
        leaf-level diff; never touches device data."""
        got = _tree_signature(self.get(name))
        want = _cfg_param_signature(cfg)
        if got == want:
            return
        diff = sorted(k for k in set(want) | set(got)
                      if want.get(k) != got.get(k))
        raise ValueError(
            f"param set {name!r} does not fit a "
            f"{pl.variant_name(cfg)!r} lane: mismatched leaves "
            f"{ {k: {'want': want.get(k), 'got': got.get(k)} for k in diff} }"
            " — the set must be initialized/trained for the tenant's "
            "attention+encoder and table dims")


class _Cohort:
    """Tenants sharing one variant + kernel tier + parameter set: stacked
    states + one vmapped step over the cohort's OWN resident params.

    With a ``reserve`` (a capacity-class policy — ``serving/admission.py``
    ``CapacityLadder``) the stacked tables are laid out with SPARE
    idle-masked slots beyond the tenants present, so attaching a tenant
    lands in an existing slot (no shape change, the compiled round keeps
    serving) and detaching one leaves its slot idle-resident; only
    exhausting the class relays out. Without a reserve (the default) the
    tables stay exactly tenant-count-sized, shrinking eagerly on removal
    — the original offline behavior."""

    def __init__(self, cfg: tgn.TGNConfig, use_kernels, params: dict,
                 reserve=None, param_set: str = DEFAULT_PARAMS):
        self.cfg = cfg
        self.reserve = reserve      # capacity-class policy or None (exact)
        self.pipeline = pl.build_pipeline(cfg, use_kernels=use_kernels)
        #: resolved kernel tier — cohorts are keyed by (cfg, tier,
        #: param_set), so a fused-lane tenant and a staged-lane tenant of
        #: the SAME variant form two lanes of the coalesced round.
        self.tier = self.pipeline.tier
        #: the cohort's resident parameter set + its registry name: every
        #: launch of this lane consumes THESE weights (the coalesced
        #: round's per-lane params dimension).
        self.params = params
        self.param_set = param_set
        # folded/packed tables prepared once per cohort; closed over (not a
        # jit argument) because the packed layouts carry static metadata.
        self.aux = self.pipeline.prepare(params)
        self.tids: list[str] = []
        self.state = None           # stacked VertexState, leaves (C, ...)
        self._build_launches()

    def _build_launches(self) -> None:
        """Compile the cohort launches (subclass hook: the sharded cohort
        rebuilds these with mesh placements and state donation)."""
        self._vstep = self.pipeline.batched_step(self.aux)

        # single-tenant peek fast path: the same vmapped computation with
        # the expand/slice fused into ONE jit, so the hot timing hook
        # (StreamingEngine.step_on_device -> fig5/6/7 sweeps) pays no
        # eager re-stacking or out-of-jit vertex-table slicing.
        step, aux = self.pipeline.step, self.aux

        def one(params, state, batch, ef, nf):
            return step(params, aux, state, batch, ef, nf)

        def one_t(params, state, batch, ef, nf):
            out = jax.vmap(one, in_axes=(None, 0, 0, None, None))(
                params, state, jax.tree.map(lambda x: x[None], batch),
                ef, nf)
            return jax.tree.map(lambda x: x[0], out)

        self._vstep1 = jax.jit(one_t)

    def _init_row(self):
        """One idle tenant's state in the tier's resident layout."""
        return self.pipeline.resident(self.pipeline.init_state())

    @property
    def size(self) -> int:
        return len(self.tids)

    @property
    def capacity(self) -> int:
        """Rows of the stacked tables: ``size`` plus any reserved
        capacity-class spares and/or mesh padding (spare slots are
        idle-masked every round — bitwise no-ops)."""
        return 0 if self.state is None else int(self.state.memory.shape[0])

    @property
    def spare(self) -> int:
        """Idle reserved slots a fast-path attach can land in."""
        return self.capacity - self.size

    def _target_capacity(self, n: int) -> int:
        """Stacked-table rows to lay out for ``n`` tenants (subclass hook:
        the sharded cohort rounds up to a mesh tenant-axis multiple).
        With a reserve policy this includes headroom slots so the next
        attaches stay inside the existing compiled program."""
        return n if self.reserve is None else self.reserve.capacity_for(n)

    def _fit(self, state):
        """Lay out freshly grown/shrunk stacked tables: pad the real
        tenant rows up to the target capacity with idle init-state rows,
        then place them (subclass hook: mesh placement)."""
        n = int(state.memory.shape[0])
        cap = self._target_capacity(len(self.tids))
        if cap > n:
            row = self._init_row()
            pads = jax.tree.map(lambda x: jnp.repeat(x[None], cap - n,
                                                     axis=0), row)
            state = jax.tree.map(lambda t, p: jnp.concatenate([t, p],
                                                              axis=0),
                                 state, pads)
        return self._place(state)

    def _place(self, state):
        """Device placement of freshly laid-out tables (subclass hook:
        the sharded cohort pins its PartitionSpecs)."""
        return state

    def ensure_capacity(self) -> None:
        """Materialize the reserve capacity with ZERO tenants (a prewarmed
        lane: the variant is resident in the compiled round before its
        first tenant arrives, so that first attach is a fast path)."""
        if self.state is None:
            empty = jax.tree.map(lambda x: x[None][:0],
                                 self._init_row())
            self.state = self._fit(empty)

    def add(self, tid: str) -> bool:
        """Attach a tenant. Returns True when the stacked tables were
        relaid out (a shape change: the coalesced round must rebuild);
        False when a reserved spare slot absorbed the attach in place —
        the fast path live admission rides on."""
        n = self.size
        if self.reserve is not None and self.state is not None \
                and self.capacity > n:
            # fast path: the new tenant's init-state row overwrites an
            # idle spare slot (spares already hold init rows, but a slot
            # freed by a detach holds the departed tenant's stale rows)
            row = self._init_row()
            self.state = self._place(jax.tree.map(
                lambda t, r: t.at[n].set(r), self.state, row))
            self.tids.append(tid)
            return False
        row = jax.tree.map(lambda x: x[None], self._init_row())
        if self.state is None:
            st = row
        else:
            real = jax.tree.map(lambda x: x[:n], self.state)
            st = jax.tree.map(lambda t, r: jnp.concatenate([t, r], axis=0),
                              real, row)
        self.tids.append(tid)
        self.state = self._fit(st)
        return True

    def remove(self, tid: str) -> bool:
        """Release the tenant's slot. Returns True when the tables were
        relaid out. Without a reserve the slot is released eagerly: the
        stacked tables shrink to the remaining tenants (plus mesh padding
        in the sharded cohort) — a departed tenant never leaves a dead row
        behind. With a reserve the LAST tenant's row swaps into the hole
        and the freed slot stays resident idle-masked, so a detach never
        changes the compiled layout."""
        i = self.tids.index(tid)
        if self.reserve is not None:
            last = len(self.tids) - 1
            if i != last:
                self.state = self._place(jax.tree.map(
                    lambda x: x.at[i].set(x[last]), self.state))
                self.tids[i] = self.tids[last]
            self.tids.pop()
            return False
        n = self.size
        self.tids.pop(i)
        if not self.tids:
            self.state = None
            return True
        keep = np.array([j for j in range(n) if j != i])
        self.state = self._fit(jax.tree.map(lambda x: x[keep], self.state))
        return True

    def launch(self, stacked_batch: tuple, edge_feats, node_feats,
               commit: bool = False) -> tgn.BatchOut:
        """One device launch advancing every tenant slot of this cohort,
        on the cohort's OWN resident parameter set. ``commit`` marks
        launches whose returned state will replace ``self.state`` (the
        sharded cohort donates the old buffers then)."""
        return self._vstep(self.params, self.state, stacked_batch,
                           edge_feats, node_feats)


class SessionManager:
    """Batched multi-tenant serving over the TGNPipeline registry.

    Many independent tenant streams over a registry of named parameter
    sets. Tenants are grouped into cohorts by (variant config, kernel
    tier, parameter set); each round, one vmapped launch per cohort
    advances every tenant (idle tenants masked). See the module docstring
    for the numerics contract.

    ::

        mgr = SessionManager(params, edge_feats, model=cfg)
        a = mgr.add_tenant()                        # base variant
        b = mgr.add_tenant("sat+lut+np4+reservoir")  # same params, new policy
        mgr.register_params("teacher-v1", teacher_params)
        c = mgr.add_tenant("teacher", params="teacher-v1")  # own weights
        outs = mgr.step({a: b1, b: b2, c: b3})       # {tid: BatchOut}
        mgr.state_of(a)                              # tenant's VertexState

    Tenants on the DEFAULT set must share the session's attention+encoder
    axes (one set cannot drive two parameter pytrees); a tenant on a
    NAMED set brings its own weights, so any registry variant may serve —
    the teacher/student A/B lanes above still advance as ONE coalesced
    launch per round.
    """

    def __init__(self, params: dict, edge_feats, node_feats=None, *,
                 model: tgn.TGNConfig | None = None, variant=None,
                 use_kernels: bool = False, coalesce: bool = True,
                 reserve=None, obs: MetricsRegistry | None = None, **dims):
        if model is None:
            if variant is None:
                raise TypeError("pass model=TGNConfig or variant= + dims")
            model = pl.variant_config(variant, **dims)
        elif variant is not None or dims:
            raise TypeError("model= is exclusive with variant=/dims")
        if reserve is True:          # convenience: the default ladder
            from repro.serving.admission import CapacityLadder
            reserve = CapacityLadder()
        #: capacity-class policy (``admission.CapacityLadder`` or any
        #: object with ``capacity_for(n)``): cohorts hold spare
        #: idle-masked lane slots so live attach/detach lands in the
        #: existing compiled round. ``None`` (default) = exact-size
        #: cohorts, eager shrink — the offline behavior.
        self.reserve = reserve
        self.base_cfg = model
        self.use_kernels = use_kernels
        self.coalesce = coalesce
        #: named, device-resident parameter sets; ``params`` becomes the
        #: DEFAULT_PARAMS entry, more arrive via ``register_params``
        self.param_store = ParamStore(params, place=self._place_params)
        self.params = self.param_store.get(DEFAULT_PARAMS)
        #: the shared edge-feature table, laid out ONCE in the kernels' row
        #: layout (``kernels/ops.row_table``): every tier reads it, and the
        #: fused kernel DMAs single rows from it without a per-step copy
        self.edge_feats = kops.row_table(jnp.asarray(edge_feats))
        self.node_feats = (jnp.asarray(node_feats)
                           if node_feats is not None else None)
        # keyed by (cfg, resolved kernel tier, param-set name): tenants
        # may pick a kernel tier (add_tenant(use_kernels=...)) and a
        # parameter set (add_tenant(params=...)) per lane, defaulting to
        # the session-wide setting / DEFAULT_PARAMS
        self._cohorts: dict[tuple, _Cohort] = {}
        self._tenant_cohort: dict[str, _Cohort] = {}
        self._next_id = 0
        self.metrics: list[dict] = []
        # coalesced-round layout (built lazily, dropped on fleet changes)
        self._coalesced: pl.CoalescedRound | None = None
        self._stager: _HostStager | None = None
        self._drained: tuple[int, float] | None = None   # summary() cache
        #: fleet-layout rebuilds of the coalesced launch (a relayout means
        #: the next round compiles a fresh program — the slow path the
        #: reserve classes exist to avoid)
        self.relayouts = 0
        #: what the last add_tenant/remove_tenant did to the layout —
        #: ``{"tid", "relayout", "new_cohort"}`` (read by the admission
        #: controller to label fast vs slow admissions)
        self.last_admission: dict | None = None
        #: per-tenant serving counters fed by ``step`` (see tenant_stats)
        self._tenant_stats: dict[str, dict] = {}
        #: live queue-depth provider (``() -> {tid: rows}``) a serving
        #: frontend registers, so ``summary()``/``tenant_stats()`` stay
        #: the one source of truth for the stats endpoint
        self.queue_depths = None
        #: the fleet's metrics registry (``obs.MetricsRegistry``) — ONE
        #: instance every layer writes through (frontend latencies,
        #: coalesced-round compile gauges, admission tallies), so
        #: ``snapshot()`` is the lock-consistent view a stats/metrics
        #: response embeds
        self.obs = obs if obs is not None else MetricsRegistry()
        #: sampled round tracer (``obs.RoundTracer``) — ``set_tracer``.
        #: None (default) keeps every round fence-free.
        self.tracer = None
        #: per-tenant latency-SLO burn tracker (``set_slo``) or None.
        self.slo = None
        self._obs_rounds = 0     # round walls already fed to registry/SLO
        #: armed fault-injection plan (``faults.FaultInjector``) or None
        #: — every hook site is gated ``if self._faults is not None:``
        #: (tools/session_lint.py rule 4), so an unarmed fleet pays one
        #: attribute test per round.
        self._faults = None
        #: supervising ``guard.FleetGuard`` (set by its constructor) or
        #: None; ``guarded_step`` routes rounds through it when present.
        self.guard = None
        #: tenants whose traffic is dropped and lane slot idle-masked
        #: (valid=False every round — the established bitwise no-op), so
        #: a sick tenant stops serving with ZERO recompiles and zero
        #: effect on cohort-mates' trajectories.
        self._quarantined: set[str] = set()

    # -- observability hooks -------------------------------------------
    def set_tracer(self, tracer) -> None:
        """Attach a sampled round tracer (``obs.RoundTracer``). Spans and
        the device drain fence happen at trace-sample rounds ONLY, so the
        async round pipeline keeps its never-block contract on every
        other round. ``None`` detaches."""
        self.tracer = tracer

    def set_slo(self, target_ms: float, objective: float = 0.99,
                source: str = "round"):
        """Arm per-tenant latency-SLO burn accounting (``obs.SLOTracker``)
        — surfaced in ``summary()["per_tenant"][tid]["slo"]`` and the
        frontend's ``metrics`` wire op. ``source`` names what one
        observation is: ``"round"`` (walls fed by ``summary()``) or
        ``"event"`` (the frontend's per-event latencies)."""
        from repro.obs import SLOTracker
        self.slo = SLOTracker(target_ms, objective=objective, source=source)
        return self.slo

    def set_faults(self, injector) -> None:
        """Arm (or with ``None`` disarm) a deterministic fault-injection
        plan (``faults.FaultInjector``) — chaos testing only; an unarmed
        session's hook sites are no-ops (docs/ROBUSTNESS.md)."""
        self._faults = injector

    # -- quarantine (the guard's isolation primitive) -------------------
    def quarantine(self, tid: str) -> None:
        """Stop serving ``tid`` WITHOUT detaching it: its batches are
        dropped from every round, so its lane slot idle-masks
        (all-``valid=False`` — a bitwise no-op on its state) while the
        compiled round keeps serving everyone else unchanged. Zero
        recompiles, zero effect on cohort-mates."""
        if tid not in self._tenant_cohort:
            raise KeyError(f"unknown tenant {tid!r}")
        self._quarantined.add(tid)
        self.obs.gauge("guard.quarantined_now").set(len(self._quarantined))

    def unquarantine(self, tid: str) -> None:
        self._quarantined.discard(tid)
        self.obs.gauge("guard.quarantined_now").set(len(self._quarantined))

    def is_quarantined(self, tid: str) -> bool:
        return tid in self._quarantined

    @property
    def quarantined(self) -> frozenset:
        return frozenset(self._quarantined)

    def guarded_step(self, batches: Mapping) -> dict:
        """``step`` routed through the supervising ``FleetGuard`` when
        one is attached (health checks, quarantine, auto-restore, tier
        degradation — serving/guard.py); plain ``step`` otherwise. The
        serving drivers (``run``, the frontend's pump) call this."""
        if self.guard is not None:
            return self.guard.step(batches)
        return self.step(batches)

    def _invalidate_layout(self) -> None:
        """Fleet layout changed: the next round builds (and compiles) a
        fresh ``CoalescedRound``. The current-launch compile gauges reset
        with it — ``compile_counters`` reports the CURRENT launch."""
        self._coalesced = None
        self.obs.gauge("compile.round_traces").set(0)
        self.obs.gauge("compile.round_calls").set(0)

    # -- tenant lifecycle ----------------------------------------------
    def _place_params(self, params: dict) -> dict:
        """Device placement of a registered parameter set (subclass hook:
        the sharded session replicates it across the mesh)."""
        return params

    def register_params(self, name: str, params: dict) -> str:
        """Register a NAMED parameter set (device-placed, immutable) for
        tenants to serve on: ``add_tenant(..., params=name)`` lands its
        tenant in a lane resident on THESE weights. Registration alone
        never touches the fleet layout — no relayout, no recompile; the
        teacher/student A/B flow is register -> (prewarm ->) attach.
        Returns ``name``."""
        self.param_store.register(name, params)
        return name

    def _make_cohort(self, cfg: tgn.TGNConfig, use_kernels,
                     param_set: str = DEFAULT_PARAMS) -> _Cohort:
        """Cohort factory (the sharded session swaps in mesh-placed ones)."""
        return _Cohort(cfg, use_kernels, self.param_store.get(param_set),
                       reserve=self.reserve, param_set=param_set)

    def _tenant_cfg(self, variant, reservoir_tau,
                    param_set: str = DEFAULT_PARAMS) -> tgn.TGNConfig:
        base = self.base_cfg
        if variant is None:
            cfg = base
        else:
            v = pl.resolve_variant(variant)
            if (v.attention, v.encoder) != (base.attention, base.encoder):
                if param_set == DEFAULT_PARAMS:
                    raise ValueError(
                        f"tenant variant {pl.variant_name(v)!r} needs "
                        f"{v.attention}+{v.encoder} parameters but this "
                        f"session shares {base.attention}+{base.encoder} "
                        "parameters; prune_k and sampler may vary per "
                        "tenant, the parameterized axes may not — unless "
                        "the tenant brings its own weights "
                        "(register_params + add_tenant(..., params=name))")
                # a named set brings its own weights: the tenant may pick
                # ANY registry variant; table/feature dims stay the
                # session's (one edge-feature store, one vertex universe)
                cfg = base.replace(attention=v.attention, encoder=v.encoder,
                                   prune_k=v.prune_k, sampler=v.sampler)
            else:
                cfg = base.replace(prune_k=v.prune_k, sampler=v.sampler)
        if reservoir_tau is not None:
            cfg = cfg.replace(reservoir_tau=reservoir_tau)
        return cfg

    def _resolve_lane(self, variant, reservoir_tau, use_kernels,
                      params) -> tuple:
        """Resolve an admission request to its lane key ``(cfg, tier,
        param-set name)``, validating the param-set binding BEFORE any
        fleet mutation (an unknown or ill-fitting set rejects cleanly —
        compile counters and the serving layout are untouched)."""
        pname = DEFAULT_PARAMS if params is None else params
        self.param_store.get(pname)          # unknown set: reject here
        cfg = self._tenant_cfg(variant, reservoir_tau, pname)
        self.param_store.check_binding(pname, cfg)
        tier = pl.stages.resolved_tier(
            cfg, self.use_kernels if use_kernels is None else use_kernels)
        return cfg, tier, pname

    def add_tenant(self, variant=None, *, name: str | None = None,
                   reservoir_tau: float | None = None,
                   use_kernels=None, params: str | None = None) -> str:
        """Register a tenant stream; returns its id.

        ``variant`` is any registry spec sharing the session's parameterized
        axes (attention+encoder); ``prune_k`` and the sampler backend may
        differ per tenant, and so may the kernel tier (``use_kernels``:
        ``"ref"``/``"staged"``/``"fused"`` or a bool; ``None`` = the
        session default) — lanes of the coalesced round select their tier
        independently. ``params`` names a registered parameter set
        (``register_params``): the tenant serves on THOSE weights, and may
        then pick any attention+encoder (teacher/student A/B lanes).
        Adding a tenant grows its cohort's stacked state (next launch
        recompiles for the new tenant count) unless a reserved spare slot
        absorbs it.
        """
        cfg, tier, pname = self._resolve_lane(variant, reservoir_tau,
                                              use_kernels, params)
        tid = name if name is not None else f"t{self._next_id}"
        self._next_id += 1
        if tid in self._tenant_cohort:
            raise ValueError(f"tenant {tid!r} already exists")
        cohort = self._cohorts.get((cfg, tier, pname))
        created = cohort is None
        if created:
            cohort = self._cohorts[(cfg, tier, pname)] = \
                self._make_cohort(cfg, tier, pname)
        relayout = cohort.add(tid)
        self._tenant_cohort[tid] = cohort
        self._tenant_stats[tid] = {"rounds": 0, "rows": 0,
                                   "last_flush_t": None}
        self.last_admission = {"tid": tid, "relayout": relayout,
                               "new_cohort": created}
        if created or relayout:
            self._invalidate_layout()    # fleet layout changed: relaunch
        return tid

    def prewarm_cohort(self, variant=None, *,
                       reservoir_tau: float | None = None,
                       use_kernels=None, params: str | None = None) -> None:
        """Materialize a variant's cohort with ZERO tenants at its reserve
        capacity: the lane is compiled into the next round while empty, so
        the FIRST tenant of that variant (and parameter set — ``params``
        names a registered set, e.g. a freshly distilled student about to
        be canaried) attaches on the fast path instead of forcing a
        mid-serving relayout. Requires ``reserve``."""
        if self.reserve is None:
            raise ValueError("prewarm_cohort needs a reserve policy "
                             "(SessionManager(reserve=...)); without spare "
                             "lane slots an empty cohort cannot admit "
                             "anything without a relayout anyway")
        cfg, tier, pname = self._resolve_lane(variant, reservoir_tau,
                                              use_kernels, params)
        if (cfg, tier, pname) in self._cohorts:
            return
        cohort = self._cohorts[(cfg, tier, pname)] = \
            self._make_cohort(cfg, tier, pname)
        cohort.ensure_capacity()
        self._invalidate_layout()        # new lane: relaunch (once, now)

    def remove_tenant(self, tid: str) -> None:
        cohort = self._tenant_cohort[tid]
        # drain in-flight async rounds BEFORE releasing the lane slot:
        # dispatched rounds still hold the cohort's stacked tables (and
        # the pending per-round edge scalars in ``metrics`` reference
        # them), so the slot's rows are shrunk/swapped away only after
        # everything in flight has landed
        self.sync()
        self._tenant_cohort.pop(tid)
        self._tenant_stats.pop(tid, None)
        if tid in self._quarantined:
            self.unquarantine(tid)
        relayout = cohort.remove(tid)
        if not cohort.tids and cohort.reserve is None:
            # reserve-less cohorts tear down when empty; reserved lanes
            # stay resident (capacity held) so re-attach is a fast path
            self._cohorts.pop((cohort.cfg, cohort.tier, cohort.param_set))
            relayout = True
        self.last_admission = {"tid": tid, "relayout": relayout,
                               "new_cohort": False}
        if relayout:
            self._invalidate_layout()    # fleet layout changed: relaunch

    def compile_counters(self) -> dict:
        """The zero-recompile guard's view: ``relayouts`` (coalesced
        layouts built), ``round_traces`` (compiled executables of the
        CURRENT round launch — one per new static widths vector), and
        ``round_calls`` (executions dispatched through it). A live
        attach/detach that landed in reserved slots leaves ``relayouts``
        and ``round_traces`` exactly where they were.

        All three come from ONE ``obs`` registry snapshot (the round
        launch maintains the gauges, ``_ensure_layout`` the counter), so
        a stats response that embeds these twice — the frontend's view
        and the admission controller's — cannot observe two mid-round
        states of the same counters."""
        snap = self.obs.snapshot(prefix="compile.")
        return {"relayouts": int(snap.get("compile.relayouts", 0)),
                "round_traces": int(snap.get("compile.round_traces", 0)),
                "round_calls": int(snap.get("compile.round_calls", 0))}

    @property
    def tenants(self) -> tuple:
        return tuple(self._tenant_cohort)

    def cohort_of(self, tid: str) -> _Cohort:
        return self._tenant_cohort[tid]

    def state_of(self, tid: str) -> mailbox.VertexState:
        """The tenant's (unbatched) VertexState view, native tables."""
        cohort = self._tenant_cohort[tid]
        i = cohort.tids.index(tid)
        return cohort.pipeline.native(
            jax.tree.map(lambda x: x[i], cohort.state))

    def set_state(self, tid: str, st: mailbox.VertexState) -> None:
        cohort = self._tenant_cohort[tid]
        i = cohort.tids.index(tid)
        cohort.state = jax.tree.map(lambda t, r: t.at[i].set(r),
                                    cohort.state,
                                    cohort.pipeline.resident(st))

    def _cohort_info(self, c: _Cohort) -> dict:
        return {"tenants": tuple(c.tids), "capacity": c.capacity,
                "param_set": c.param_set, **c.pipeline.describe()}

    def describe(self) -> dict:
        """Cohort layout: variant -> (tenant ids, parameter set, resolved
        stage backends). Cohorts that differ only in ``reservoir_tau``,
        parameter set, or kernel tier share a variant name; the later ones
        are disambiguated with ``@tau=`` / ``@params=`` / ``@<tier>``
        suffixes so no cohort's entry is silently overwritten."""
        out, holders = {}, {}
        for c in self._cohorts.values():
            key = base = c.pipeline.variant
            if key in out:
                first = holders[base]
                if c.cfg.reservoir_tau != first.cfg.reservoir_tau:
                    key = f"{base}@tau={c.cfg.reservoir_tau:g}"
                if key in out and c.param_set != first.param_set:
                    key = f"{key}@params={c.param_set}"
                if key in out:
                    key = f"{key}@{c.tier}"
            holders.setdefault(base, c)
            out[key] = self._cohort_info(c)
        return out

    # -- the round step ------------------------------------------------
    def _cohort_round(self, cohort: _Cohort, submitted: dict,
                      commit: bool = False) -> tgn.BatchOut:
        B = max(d[0].shape[0] for d in submitted.values())
        devs = [( _pad_dev(submitted[tid], B) if tid in submitted
                  else _idle_dev(B)) for tid in cohort.tids]
        # mesh-padding slots of a sharded cohort idle every round
        devs += [_idle_dev(B)] * (cohort.capacity - len(devs))
        stacked = tuple(jnp.stack([d[j] for d in devs])
                        for j in range(5))
        return cohort.launch(stacked, self.edge_feats, self.node_feats,
                             commit=commit)

    @staticmethod
    def _slice_out(out: tgn.BatchOut, i: int, b: int,
                   with_state: bool = False) -> tgn.BatchOut:
        """Tenant ``i``'s unbatched BatchOut, cut back to its own ``b`` rows
        (the 2B-row distill views are concat([src rows, dst rows])).

        ``step`` returns outputs with ``state=None``: per-tenant states are
        committed inside the session (read them via ``state_of``), and
        slicing full vertex tables out of the stacked pytree per tenant per
        round would dwarf the step itself. ``peek`` keeps the state leaf.
        """
        st = (jax.tree.map(lambda x: x[i], out.state) if with_state
              else None)
        one = tgn.BatchOut(state=st, emb_src=out.emb_src[i],
                           emb_dst=out.emb_dst[i],
                           attn_logits=out.attn_logits[i],
                           nbr_valid=out.nbr_valid[i],
                           nbr_dt=out.nbr_dt[i])
        B = one.emb_src.shape[0]
        if b == B:
            return one
        two = jnp.concatenate([jnp.arange(b), B + jnp.arange(b)])
        return tgn.BatchOut(
            state=one.state, emb_src=one.emb_src[:b], emb_dst=one.emb_dst[:b],
            attn_logits=one.attn_logits[two], nbr_valid=one.nbr_valid[two],
            nbr_dt=one.nbr_dt[two])

    # -- coalesced dispatch (the default round path) -------------------
    def _make_coalesced(self) -> pl.CoalescedRound:
        """Build the fused whole-round launch for the current fleet layout
        (subclass hook: the sharded session pins mesh placements and
        donates the resident state buffers)."""
        return pl.CoalescedRound(((c.pipeline, c.aux, c.capacity)
                                  for c in self._cohorts.values()),
                                 obs=self.obs)

    def _make_stager(self, rows: int, width: int) -> _HostStager:
        """Host-stager factory (subclass hook: mesh batch placements)."""
        return _HostStager(rows, width)

    def _ensure_layout(self, width: int) -> pl.CoalescedRound:
        if self._coalesced is None:
            self._coalesced = self._make_coalesced()
            self.relayouts += 1
            self.obs.counter("compile.relayouts").inc()
        if self._stager is None or self._stager.rows != self._coalesced.rows:
            self._stager = self._make_stager(self._coalesced.rows, width)
        self._stager.ensure_width(width)
        return self._coalesced

    def _coalesced_round(self, batches: Mapping,
                         trace=None) -> tuple[dict, object]:
        """ONE compiled launch for the whole round: stage every submitted
        batch into the super-batch ring buffer in place (single
        ``device_put``), advance all cohorts through the fused launch, and
        commit each cohort's state. Returns ``(outs, pending edge count)``
        — the count is a device scalar resolved only in ``summary()``.

        ``trace`` is the sampled-round tracer handle (None on unsampled
        rounds — the fast path): stage/launch host spans plus an ``h2d``
        fence attributing where the super-batch transfer actually landed.
        Every fence sits inside the ``trace`` gate, so unsampled rounds
        never block (``tools/session_lint.py`` enforces this)."""
        host = {tid: _as_host_tuple(b) for tid, b in batches.items()}
        width = max(h[0].shape[0] for h in host.values())
        launch = self._ensure_layout(width)
        cohorts = list(self._cohorts.values())
        offsets, lo = {}, 0
        for c in cohorts:
            offsets[id(c)] = lo
            lo += c.capacity
        rows = {}
        widths = {}
        for tid, h in host.items():
            c = self._tenant_cohort[tid]
            rows[offsets[id(c)] + c.tids.index(tid)] = h
            widths[id(c)] = max(widths.get(id(c), 1), h[0].shape[0])
        with span("session.stage", trace, rows=len(rows),
                  width=width) as staged:
            superbatch = self._stager.stage(rows)
        with span("session.dispatch", trace, lanes=len(cohorts)):
            states = tuple(c.state for c in cohorts)
            # per-segment padded widths (static): each cohort steps at ITS
            # round-max batch size — the exact B the per-cohort launch
            # would use, which the bitwise contract requires (idle cohorts
            # run a width-1 masked no-op lane). Params are per-lane too:
            # each segment consumes its cohort's resident set
            # (teacher/student A/B lanes in the same launch).
            outs_t, edges = launch(tuple(c.params for c in cohorts), states,
                                   superbatch, self.edge_feats,
                                   self.node_feats,
                                   widths=tuple(widths.get(id(c), 1)
                                                for c in cohorts))
            # the staged set may zero-copy alias host memory: its reuse
            # must also wait for this launch, not just the transfer. Gate
            # on the edge-count output — the state outputs become DONATED
            # inputs of the next round (sharded cohorts), which
            # block_until_ready rejects
            self._stager.note_consumer(edges)
        if trace is not None:
            # H2D overlap attribution: the super-batch transfer was
            # dispatched inside stage; only fencing it (sampled rounds
            # only) shows how far past the dispatch it actually landed
            jax.block_until_ready(superbatch)
            trace.add("h2d", staged.t0, trace.clock(), cat="device",
                      rows=len(rows))
        outs: dict[str, tgn.BatchOut] = {}
        with span("session.outputs"):
            splits = self.obs.counter("session.output_splits")
            trims = self.obs.counter("session.output_trims")
            for c, out in zip(cohorts, outs_t):
                c.state = out.state
                mine = [(i, tid) for i, tid in enumerate(c.tids)
                        if tid in host]
                if not mine:
                    continue
                # one compiled split per cohort; picking the submitted
                # slots out of its result is host-side tuple indexing
                slots = _split_out(tuple(getattr(out, f)
                                         for f in _OUT_LEAVES))
                splits.inc()
                for i, tid in mine:
                    leaves = slots[i]
                    b = host[tid][0].shape[0]
                    if b < widths[id(c)]:
                        leaves = _trim_out(b, leaves)
                        trims.inc()
                    outs[tid] = tgn.BatchOut(None, *leaves)
        return outs, edges

    def lower_round(self, width: int):
        """The fleet's coalesced round lowered, not run, with every lane
        at batch ``width`` — what one such round compiles to."""
        launch = self._ensure_layout(width)
        cohorts = list(self._cohorts.values())
        return launch.lower(tuple(c.params for c in cohorts),
                            tuple(c.state for c in cohorts),
                            self._stager.stage({}), self.edge_feats,
                            self.node_feats,
                            widths=(width,) * len(cohorts))

    def _device_staged(self, batches: Mapping) -> bool:
        """True when the fleet is a single-tenant view being fed an
        already-on-device batch tuple (StreamingEngine's prefetched
        path): round-tripping it through the host stager would cost a
        blocking D2H copy plus a second transfer, so such steps launch
        through the per-cohort dispatch instead — a one-cohort fleet, so
        still exactly one compiled launch per round."""
        if len(batches) != 1 or len(self._tenant_cohort) != 1:
            return False
        (b,) = batches.values()
        return (isinstance(b, tuple) and len(b) == 5
                and all(x is None or isinstance(x, jax.Array) for x in b))

    def _percohort_round(self, batches: Mapping) -> tuple[dict, object, int]:
        """The original dispatch — one compiled launch per cohort, batches
        staged through per-tenant device ops. Kept (``coalesce=False``) as
        the measured baseline of the coalesced path; trajectories are
        bitwise-identical between the two (tests/test_session.py)."""
        outs: dict[str, tgn.BatchOut] = {}
        launches = 0
        edge_counts = []
        for cohort in self._cohorts.values():
            submitted = {tid: _as_device_tuple(batches[tid])
                         for tid in cohort.tids if tid in batches}
            if not submitted:
                continue
            out = self._cohort_round(cohort, submitted, commit=True)
            cohort.state = out.state
            launches += 1
            for i, tid in enumerate(cohort.tids):
                if tid in submitted:
                    b = submitted[tid][0].shape[0]
                    outs[tid] = self._slice_out(out, i, b)
                    edge_counts.append(submitted[tid][4].sum())
        # pending device-side count — resolved in summary(), never here
        edges = jnp.stack(edge_counts).sum() if edge_counts else 0
        return outs, edges, launches

    def step(self, batches: Mapping[str, EdgeBatch | tuple]) -> dict:
        """Advance every tenant with a submitted batch. Coalesced (the
        default), the whole round — every cohort, idle members masked — is
        ONE compiled launch fed by one in-place-staged ``device_put``;
        with ``coalesce=False`` each submitted cohort launches separately.
        Returns ``{tid: BatchOut}`` for the submitted tenants with
        ``state=None`` — per-tenant states are committed in place; read
        them via ``state_of``. Coalesced, the outputs come from ONE
        compiled split per submitting cohort (``_split_out``), plus one
        compiled trim (``_trim_out``) for each tenant that submitted
        fewer rows than its cohort's round width; the registry counts
        both (``session.output_splits``, ``session.output_trims``).

        Steps are fully asynchronous: nothing here blocks on the device,
        so staging round k+1 overlaps the compute of round k. ``sync()``
        (or ``summary()``, which calls it) drains the fleet.

        The step and its phases are profiler spans (``obs.span``):
        ``session.step`` (argument ``round``, the round's index) holds
        ``session.stage`` (with ``session.stage_wait``, the stager's reuse
        gate), ``session.dispatch`` and ``session.outputs``.
        """
        with span("session.step", round=len(self.metrics)):
            unknown = set(batches) - set(self._tenant_cohort)
            if unknown:
                raise KeyError(f"unknown tenants {sorted(unknown)}; "
                               f"registered: {sorted(self._tenant_cohort)}")
            if self._faults is not None:
                # chaos-only injection hook: one attribute test when unarmed
                batches = self._faults.on_round(self, batches)
            if self._quarantined:
                # quarantined traffic is dropped; the sick lane slot idle-
                # masks below (valid=False), a bitwise no-op on its state
                batches = {t: b for t, b in batches.items()
                           if t not in self._quarantined}
            trace = None
            if self.tracer is not None and batches:
                # sampled-trace gate: on unsampled rounds ``trace`` stays
                # None and the round dispatches fence-free, preserving the
                # async pipeline (and the pending edge scalars) untouched
                trace = self.tracer if self.tracer.sample_round() else None
            t0 = time.perf_counter()
            if self._faults is not None:
                self._faults.before_launch(self)   # may raise KernelFault
            if not batches:
                outs, edges, launches = {}, 0, 0
            elif self.coalesce and not self._device_staged(batches):
                outs, edges = self._coalesced_round(batches, trace=trace)
                launches = 1
            else:
                outs, edges, launches = self._percohort_round(batches)
            dt = time.perf_counter() - t0
            self._drained = None
            self.metrics.append({
                "t0": t0, "latency_s": dt, "edges": edges,
                "launches": launches, "tenants_active": len(outs),
                "tids": tuple(batches)})
            self.obs.counter("session.rounds").inc()
            self.obs.counter("session.launches").inc(launches)
            for tid, b in batches.items():
                rows = (b.src if isinstance(b, EdgeBatch) else b[0]).shape[0]
                ts = self._tenant_stats[tid]
                ts["rounds"] += 1
                ts["rows"] += int(rows)
                ts["last_flush_t"] = t0
            if trace is not None:
                # drain fence, sampled rounds ONLY: wait for this round's
                # commits so its device time is attributed to a span
                t_drain = trace.clock()
                jax.block_until_ready(tuple(c.state
                                            for c in self._cohorts.values()
                                            if c.state is not None))
                trace.add("drain", t_drain, trace.clock(), cat="device",
                          round=len(self.metrics) - 1)
            return outs

    def sync(self) -> None:
        """Drain the fleet: wait until every dispatched round's commits
        (and staged transfers) have landed. Steps never block — this is
        the one place the serving loop waits on the device."""
        for c in self._cohorts.values():
            if c.state is not None:
                jax.block_until_ready(c.state)
        if self._stager is not None:
            self._stager.drain()

    def peek(self, tid: str, batch) -> tgn.BatchOut:
        """The tenant's step output WITHOUT committing any state (timing /
        what-if hook; other cohort members are masked as idle)."""
        cohort = self._tenant_cohort[tid]
        dev = _as_device_tuple(batch)
        if cohort.size == 1 and cohort.capacity == 1:
            out = cohort._vstep1(cohort.params, cohort.state, dev,
                                 self.edge_feats, self.node_feats)
        else:
            out = self._slice_out(self._cohort_round(cohort, {tid: dev}),
                                  cohort.tids.index(tid), dev[0].shape[0],
                                  with_state=True)
        return out._replace(state=cohort.pipeline.native(out.state))

    # -- stream driving ------------------------------------------------
    def run(self, streams: Mapping[str, Iterable]):
        """Drive tenant streams round-robin until all are exhausted.

        ``streams``: tid -> iterable of EdgeBatch. Yields
        ``(batches, outs)`` per round; tenants whose stream has ended are
        masked for the remaining rounds.
        """
        its = {tid: iter(s) for tid, s in streams.items()}
        while its:
            batches = {}
            for tid in list(its):
                try:
                    batches[tid] = next(its[tid])
                except StopIteration:
                    del its[tid]
            if not batches:
                return
            yield batches, self.guarded_step(batches)

    def tenant_stats(self) -> dict:
        """Per-tenant serving metrics — ``{tid: {queue_depth, rounds,
        rows, last_flush_t[, slo]}}``: the frontend's live ingest-queue
        depth (0 unless a frontend registered its ``queue_depths``
        provider), rounds participated, rows submitted (padding
        included), the wall clock of the last round the tenant joined,
        and — when ``set_slo`` armed a tracker — the tenant's SLO burn
        view (EVERY tenant reports one, zero-observation tenants
        included). This is the one source of truth the frontend's stats
        endpoint reads."""
        qd = dict(self.queue_depths()) if self.queue_depths else {}
        slo = self.slo
        guard = self.guard
        return {tid: {"queue_depth": int(qd.get(tid, 0)), **st,
                      "quarantined": tid in self._quarantined,
                      **({"slo": slo.tenant(tid)} if slo is not None
                         else {}),
                      **({"guard": guard.tenant_view(tid)}
                         if guard is not None else {})}
                for tid, st in self._tenant_stats.items()}

    def summary(self) -> dict:
        """Aggregate round metrics (first round skipped: jit warmup),
        plus ``per_tenant`` serving counters (``tenant_stats``).

        Steps are async, so per-round walls are reconstructed from the
        dispatch timestamps — ``wall(k) = t0(k+1) - t0(k)``, with the last
        round absorbing the final ``sync()`` drain — and the pending
        device-side edge counts are resolved here, the serving loop's only
        host sync. Call right after the last round for faithful numbers.
        """
        if len(self.metrics) < 2:
            return {}
        if self._drained is None or self._drained[0] != len(self.metrics):
            self.sync()
            self._drained = (len(self.metrics), time.perf_counter())
        t0s = [m["t0"] for m in self.metrics] + [self._drained[1]]
        walls = np.diff(np.array(t0s))[1:]
        # one Histogram replaces the hand-rolled percentile math; a
        # registry-resident copy accumulates across summary() calls for
        # the metrics endpoint, and a round-sourced SLO tracker observes
        # each participating tenant's wall. Both are fed exactly once
        # per round (the cursor) — the last wall's drain component may
        # shift if more rounds arrive, an accepted approximation.
        wall_h = Histogram("session.round_wall_s")
        for w in walls:
            wall_h.record(w)
        reg_h = self.obs.histogram("session.round_wall_s")
        slo = self.slo if (self.slo is not None
                           and self.slo.source == "round") else None
        for i in range(self._obs_rounds, len(walls)):
            reg_h.record(walls[i])
            if slo is not None:
                for tid in self.metrics[i + 1].get("tids", ()):
                    if tid in self._tenant_cohort:
                        slo.observe(tid, float(walls[i]))
        self._obs_rounds = len(walls)
        edges = sum(int(np.asarray(m["edges"])) for m in self.metrics[1:])
        return {
            "rounds": len(walls),
            "tenants": len(self._tenant_cohort),
            "cohorts": len(self._cohorts),
            # max, not last: tail rounds of uneven streams mask whole
            # cohorts, which would under-report the steady-state cost
            "launches_per_round": max(m["launches"]
                                      for m in self.metrics[1:]),
            "mean_round_ms": (wall_h.mean() or 0.0) * 1e3,
            "p99_round_ms": (wall_h.quantile(0.99) or 0.0) * 1e3,
            "throughput_eps": (float(edges / wall_h.total)
                               if wall_h.total > 0 else 0.0),
            "per_tenant": self.tenant_stats(),
        }
