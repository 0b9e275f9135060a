"""The pluggable TGN pipeline: one Algorithm-1 composition for every variant.

The paper's co-design is a ladder of variants (Table II):

    vanilla+cosine  ->  sat+cosine  ->  sat+lut  ->  sat+lut+np{6,4,2}

Historically the repo implemented Algorithm 1 twice — a reference path in
``tgn.process_batch`` and a hand-fused copy inside the streaming engine that
only ran the SAT+LUT student. This module replaces both with ONE composition
of the stage interfaces in ``core/stages.py``:

    pipe = build_pipeline("sat+lut+np4", n_nodes=..., n_edges=...)
    aux  = pipe.prepare(params)                  # folded/packed tables
    out  = pipe.step(params, aux, state, batch, edge_feats)   # BatchOut

``tgn.process_batch`` is now the registry's reference composition and
``serving.StreamingEngine`` is a thin stateful session over any built
pipeline (kernel or reference backend, any variant, teacher included).

Variant registry: canonical specs are
``"<attention>+<encoder>[+np<k>][+<sampler>]"`` (sampler backends:
``stages.SAMPLERS`` — e.g. ``"sat+lut+np4+reservoir"``); Table-II row names
and a few shorthands are registered as aliases. New variants (samplers,
aggregators, encoders) plug in via ``register_variant`` without forking the
step function. Invalid specs raise with the full token menu
(``spec_menu()``).
"""
from __future__ import annotations

import functools
from typing import Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mailbox, memory, stages, tgn


class VariantSpec(NamedTuple):
    """The three model axes of the paper's ablation ladder, plus the
    serving-layer sampler-backend axis (selection policy of
    prune-then-fetch; see ``stages.SAMPLERS``)."""
    attention: str          # "vanilla" | "sat"
    encoder: str            # "cosine" | "lut"
    prune_k: int | None     # None | 6 | 4 | 2
    sampler: str = "recent"  # "recent" | "uniform" | "reservoir"


_REGISTRY: dict[str, VariantSpec] = {}
_ALIASES: dict[str, str] = {}


def spec_menu() -> str:
    """The full menu of valid variant-spec tokens — every spec-parsing
    error embeds this so an invalid string prints everything legal."""
    return (
        "valid spec grammar: '<attention>+<encoder>[+np<k>][+<sampler>]' "
        "with attention in ('vanilla', 'sat'), encoder in ('cosine', 'lut'), "
        "np<k> an integer pruning budget (SAT only, e.g. np4), and sampler "
        f"in {stages.SAMPLERS} (SAT only; default 'recent'); "
        f"registered variants: {sorted(_REGISTRY)}; "
        f"aliases: {sorted(_ALIASES)}")


def register_variant(name: str, spec: VariantSpec,
                     aliases: tuple[str, ...] = ()) -> None:
    """Register a canonical variant name (and optional aliases)."""
    _REGISTRY[name] = spec
    for a in aliases:
        _ALIASES[a] = name


register_variant("vanilla+cosine", VariantSpec("vanilla", "cosine", None),
                 aliases=("teacher", "baseline", "Baseline", "vanilla"))
register_variant("sat+cosine", VariantSpec("sat", "cosine", None),
                 aliases=("+SAT", "sat"))
register_variant("sat+lut", VariantSpec("sat", "lut", None),
                 aliases=("+LUT",))
register_variant("sat+lut+np6", VariantSpec("sat", "lut", 6),
                 aliases=("+NP(L)", "np6"))
register_variant("sat+lut+np4", VariantSpec("sat", "lut", 4),
                 aliases=("+NP(M)", "np4", "student"))
register_variant("sat+lut+np2", VariantSpec("sat", "lut", 2),
                 aliases=("+NP(S)", "np2"))
# sampler-backend variants: the student ladder with the prune-then-fetch
# selection policy swapped (multi-tenant serving mixes these per tenant)
register_variant("sat+lut+np4+uniform", VariantSpec("sat", "lut", 4,
                                                    "uniform"),
                 aliases=("uniform",))
register_variant("sat+lut+np4+reservoir", VariantSpec("sat", "lut", 4,
                                                      "reservoir"),
                 aliases=("reservoir",))

#: Canonical registry names in ladder order (Table II rows).
VARIANTS = ("vanilla+cosine", "sat+cosine", "sat+lut",
            "sat+lut+np6", "sat+lut+np4", "sat+lut+np2")

#: Sampler-backend specs of the np4 student (registry names).
SAMPLER_VARIANTS = ("sat+lut+np4", "sat+lut+np4+uniform",
                    "sat+lut+np4+reservoir")


def resolve_variant(spec) -> VariantSpec:
    """Accepts a canonical name, an alias, a generic ``attn+enc[+npK]``
    string, a VariantSpec, or a TGNConfig."""
    if isinstance(spec, VariantSpec):
        return spec
    if isinstance(spec, tgn.TGNConfig):
        return VariantSpec(spec.attention, spec.encoder, spec.prune_k,
                           spec.sampler)
    if not isinstance(spec, str):
        raise TypeError(f"cannot resolve variant from {type(spec)!r}")
    name = _ALIASES.get(spec, spec)
    if name in _REGISTRY:
        return _REGISTRY[name]
    return _parse_spec(spec)


def _parse_spec(spec: str) -> VariantSpec:
    """Grammar fallback: ``<attention>+<encoder>[+np<k>][+<sampler>]``."""
    parts = spec.split("+")
    if len(parts) not in (2, 3, 4):
        raise ValueError(f"unknown variant {spec!r}; {spec_menu()}")
    attention, encoder = parts[0], parts[1]
    if attention not in ("vanilla", "sat"):
        raise ValueError(f"unknown attention {attention!r} in {spec!r}; "
                         f"{spec_menu()}")
    if encoder not in ("cosine", "lut"):
        raise ValueError(f"unknown encoder {encoder!r} in {spec!r}; "
                         f"{spec_menu()}")
    if attention == "vanilla" and encoder != "cosine":
        raise ValueError("vanilla attention requires the cosine encoder "
                         f"(its K/Q/V inputs consume the cosine encoding "
                         f"directly; LUT is a SAT-path optimization) — "
                         f"got {spec!r}; {spec_menu()}")
    prune_k = None
    sampler = None
    for clause in parts[2:]:
        if clause.startswith("np") and clause[2:].isdigit():
            if prune_k is not None:
                raise ValueError(f"duplicate prune clause {clause!r} in "
                                 f"{spec!r}; {spec_menu()}")
            prune_k = int(clause[2:])
            if attention != "sat":
                raise ValueError("neighbor pruning requires SAT "
                                 f"(prune-then-fetch) — got {spec!r}; "
                                 f"{spec_menu()}")
        elif clause in stages.SAMPLERS:
            if sampler is not None:
                raise ValueError(f"duplicate sampler clause {clause!r} in "
                                 f"{spec!r}; {spec_menu()}")
            sampler = clause
            if attention != "sat" and clause != "recent":
                raise ValueError(
                    "alternative sampler backends require SAT "
                    f"(prune-then-fetch) — got {spec!r}; {spec_menu()}")
        else:
            raise ValueError(f"bad clause {clause!r} in {spec!r}; "
                             f"{spec_menu()}")
    return VariantSpec(attention, encoder, prune_k,
                       sampler if sampler is not None else "recent")


def variant_name(spec) -> str:
    """Canonical registry string for a spec/config (synthesized via the
    grammar when not pre-registered)."""
    v = resolve_variant(spec)
    for name, s in _REGISTRY.items():
        if s == v:
            return name
    base = f"{v.attention}+{v.encoder}"
    if v.prune_k is not None:
        base += f"+np{v.prune_k}"
    if v.sampler != "recent":
        base += f"+{v.sampler}"
    return base


def variant_config(spec, **dims) -> tgn.TGNConfig:
    """TGNConfig for a variant at the given table/feature dims.

    ``dims`` are TGNConfig fields (n_nodes, n_edges, f_edge, f_mem, ...);
    the three variant axes come from ``spec``.
    """
    v = resolve_variant(spec)
    return tgn.TGNConfig(**dims, attention=v.attention, encoder=v.encoder,
                         prune_k=v.prune_k, sampler=v.sampler)


# ---------------------------------------------------------------------------
# The composed pipeline
# ---------------------------------------------------------------------------


class TGNPipeline:
    """Algorithm 1 as a composition of registered stages.

    Pure-function API (jit/grad friendly):
      prepare(params) -> aux                       derived tables
      step(params, aux, state, batch, edge_feats, node_feats) -> BatchOut
      embed(params, aux, state, edge_feats, node_feats, vids, t) -> (h, ...)

    ``batch`` is ``(src, dst, eid, ts, valid)`` with ``valid`` optionally
    None. Convenience wrappers ``init_params``/``init_state``/``step_fn``
    cover the common cases.
    """

    def __init__(self, cfg: tgn.TGNConfig, use_kernels=False):
        self.cfg = cfg
        self.use_kernels = stages.kernel_tier(use_kernels)
        #: the tier that actually runs (``"fused"`` degrades to
        #: ``"staged"`` outside the fused kernel's coverage)
        self.tier = stages.resolved_tier(cfg, use_kernels)
        self.variant = variant_name(cfg)
        self.stages = stages.build_stages(cfg, use_kernels)
        self.prepare = stages.make_prepare(cfg, use_kernels)

    # -- construction helpers ------------------------------------------
    def init_params(self, key: jax.Array, dt_samples=None) -> dict:
        return tgn.init_params(key, self.cfg, dt_samples=dt_samples)

    def init_state(self) -> mailbox.VertexState:
        return tgn.init_state(self.cfg)

    def resident(self, state: mailbox.VertexState) -> mailbox.VertexState:
        """``state`` in the layout this tier keeps resident between steps:
        the fused tier holds memory and mailbox in the kernel's row layout
        (``stages.row_state``), every other tier the native one."""
        return stages.row_state(state) if self.tier == "fused" else state

    def native(self, state: mailbox.VertexState) -> mailbox.VertexState:
        """Inverse of ``resident``: the native ``(V, f)`` tables."""
        return (stages.native_state(state, self.cfg) if self.tier == "fused"
                else state)

    # -- Algorithm 1 ---------------------------------------------------
    def step(self, params: dict, aux: dict, state: mailbox.VertexState,
             batch, edge_feats: jax.Array,
             node_feats: jax.Array | None = None) -> tgn.BatchOut:
        """Process one chronological batch of edges (B,).

        Intra-batch temporal dependencies between vertices are ignored
        (paper's general setup) but commits are chronological with
        last-write-wins per vertex. ``valid`` masks padding rows: their
        state writes are dropped entirely (their embeddings are still
        computed but are garbage the caller must mask).
        """
        src, dst, eid, ts, valid = batch
        B = src.shape[0]
        vids = jnp.concatenate([src, dst])          # (2B,) involved instances
        t_inst = jnp.concatenate([ts, ts])
        vvalid = (jnp.concatenate([valid, valid]) if valid is not None
                  else jnp.ones((2 * B,), bool))
        st = self.stages

        # --- fused tier: the whole post-prune datapath is ONE launch ------
        # (selection metadata + winner-row DMA + EU + MUU inside the
        # kernel; commits and the ring insert follow — see
        # stages.make_fused_step)
        if st.fused is not None:
            return st.fused(params, aux, state, batch, vids, t_inst,
                            vvalid, edge_feats, node_feats)

        # --- 1. UPDT: consume cached mail for involved vertices ----------
        s_upd, lu_upd = st.memory_updater(params, aux, state, vids)

        # --- 2. chronological commit of memory (winners computed ONCE) ---
        # duplicates of a vertex consume the SAME cached mail -> identical
        # values; last-write-wins picks one winner so the scatter is
        # collision-free. The same winner mask serves the mail commit below.
        winners = st.committer.winners(vids, vvalid, B)
        state = st.committer.commit_memory(state, vids, winners, s_upd,
                                           lu_upd)

        # --- 3. GNN embeddings (sampler + aggregator on updated memory) --
        nb = st.sampler(params, aux, state, edge_feats, vids, t_inst)
        s_self = state.memory[vids]
        f_self = stages.node_rows(node_feats, vids, self.cfg.f_feat)
        h, logits = st.aggregator(params, aux, nb, s_self, f_self)

        # --- 4. cache new messages (Most-Recent aggregator == LWW commit) -
        mem_t = state.memory
        fe = stages.edge_rows(edge_feats, eid, self.cfg.f_edge)
        mail_src = memory.build_mail_raw(mem_t[src], mem_t[dst], fe)
        mail_dst = memory.build_mail_raw(mem_t[dst], mem_t[src], fe)
        new_mail = jnp.concatenate([mail_src, mail_dst], axis=0)
        state = st.committer.commit_mail(state, vids, winners, new_mail,
                                         t_inst)

        # --- 5. neighbor ring-buffer insertion (FIFO sampler) -------------
        state = mailbox.insert_neighbors(state, src, dst, eid, ts, valid)

        return tgn.BatchOut(state=state, emb_src=h[:B], emb_dst=h[B:],
                            attn_logits=logits, nbr_valid=nb.full_valid,
                            nbr_dt=nb.full_dt)

    def embed(self, params: dict, aux: dict, state: mailbox.VertexState,
              edge_feats: jax.Array, node_feats: jax.Array | None,
              vids: jax.Array, t_query: jax.Array):
        """Dynamic embeddings for vertex instances without a state update
        (negative-destination scoring, ad-hoc queries).

        Returns ``(h, logits, valid, dt)`` like the GNN stage of ``step``.
        """
        nb = self.stages.sampler(params, aux, state, edge_feats, vids,
                                 t_query)
        s_self = state.memory[vids]
        f_self = stages.node_rows(node_feats, vids, self.cfg.f_feat)
        h, logits = self.stages.aggregator(params, aux, nb, s_self, f_self)
        return h, logits, nb.full_valid, nb.full_dt

    def step_fn(self, params: dict, state: mailbox.VertexState, batch,
                edge_feats: jax.Array,
                node_feats: jax.Array | None = None) -> tgn.BatchOut:
        """``step`` with aux derived in-trace (training/reference paths:
        gradients flow through the LUT folds)."""
        return self.step(params, self.prepare(params), state, batch,
                         edge_feats, node_feats)

    def batched_step(self, aux: dict, *, donate_state: bool = False,
                     in_shardings=None, out_shardings=None, tenant_map=None):
        """The cohort launch: ``jit(vmap(step))`` over a leading tenant axis.

        Signature of the returned callable:
        ``(params, stacked_state, stacked_batch, edge_feats, node_feats)
        -> BatchOut`` with state/batch/output leaves carrying the tenant
        axis and params/features broadcast. ``aux`` (folded/packed tables
        with static metadata) is closed over, not traced.

        ``donate_state`` donates the stacked VertexState buffers to the
        launch — the committed state reuses them, so a resident fleet's
        tables are updated in place instead of double-buffered.
        ``in_shardings``/``out_shardings`` pin the mesh placement of every
        operand (the sharded tenant fabric, serving/cluster.py); left
        ``None`` the launch follows its inputs (single-device serving).
        ``tenant_map`` (``tgn_sharding.tenant_map``) wraps the vmapped
        step to run per device on a mesh.
        """
        step = self.step

        def one(params, state, batch, ef, nf):
            return step(params, aux, state, batch, ef, nf)

        vstep = jax.vmap(one, in_axes=(None, 0, 0, None, None))
        if tenant_map is not None:
            vstep = tenant_map(vstep)
        kw = {}
        if in_shardings is not None:
            kw["in_shardings"] = in_shardings
        if out_shardings is not None:
            kw["out_shardings"] = out_shardings
        if donate_state:
            kw["donate_argnums"] = (1,)
        return jax.jit(vstep, **kw)

    def describe(self) -> dict:
        """Variant + resolved stage backends (introspection/logging)."""
        return {"variant": self.variant, "use_kernels": self.use_kernels,
                "tier": self.tier, "lane": self.stages.variant_id,
                **self.stages.names}


class CoalescedRound:
    """ONE compiled launch advancing EVERY cohort of a serving round.

    The per-cohort launch (``batched_step``) pays one dispatch per cohort
    per round — the dispatch-bound regime StreamTGN identifies for small
    streaming batches. ``CoalescedRound`` fuses the whole round: the
    cohorts are laid out as contiguous row segments of a common
    **super-batch** (rows = sum of cohort capacities, columns = the shared
    padded batch width) and one ``jax.jit`` compiles every segment's
    vmapped step side by side, so a round costs one XLA execution no
    matter how many variants the fleet mixes.

    Variant-stage selection is POSITIONAL and static: each segment's rows
    are advanced by the step closure of the pipeline that built it, bound
    at trace time. ``lane_ids[row]`` (the ``stages.variant_id`` of the
    program advancing that row) is the introspection/guard view of that
    mapping — tests and ``describe`` read it; the launch itself never
    branches on it. A traced per-row ``lax.switch`` would be the dynamic
    alternative, but under ``vmap`` a batched branch index lowers to
    computing every branch for every row and selecting — cohorts ×
    variants work, the opposite of a fusion win — so rows are instead
    pinned to their lane at build time and a lane change is a relayout
    (recompile), exactly like cohort growth today.

    Cohort states stay resident per cohort (``states`` is a tuple aligned
    with the segments — no per-round concatenation of the big vertex
    tables); the super-batch is the only physically fused operand. Pad
    rows (idle tenants, mesh padding, batch-width padding) are
    all-``valid=False`` lanes: the LWW committer and the OOB-redirected
    ring insert make them bitwise no-ops, so per-tenant trajectories are
    identical to the per-cohort launches.

    **Per-lane parameter sets.** ``params`` is a tuple aligned with the
    segments, exactly like ``states``: each segment's vmapped step
    consumes ITS cohort's resident parameter set as a traced operand —
    the same position ``batched_step`` passes it — so a teacher lane and
    two distilled-student lanes (different weights, even different
    attention/encoder pytrees) advance in the SAME compiled launch while
    every segment program stays shape-identical to its per-cohort
    launch (the bitwise contract). A single mapping broadcasts to every
    lane (the shared-params fleet, the pre-param-store behavior).

    **Reserved lane slots (live admission).** A segment's ``rows`` is a
    *capacity*, not a head-count: the serving session may lay a cohort
    out with spare idle-masked slots (``serving/admission.py`` capacity
    classes). Attaching a tenant into a spare slot — or detaching one and
    leaving its slot idle — changes nothing this class was built from, so
    the SAME compiled program keeps serving: no relayout, no recompile,
    no round stall. Only exhausting a capacity class forces a new
    ``CoalescedRound`` (the slow path, identical to cohort growth).
    ``traces`` counts compilations of this launch (the body traces once
    per new static signature), so serving tests can assert live admission
    never recompiled: a fast attach/detach leaves ``traces`` untouched.

    Calling convention::

        outs, edges = round(params, states, superbatch, edge_feats,
                            node_feats)

    ``params`` is a per-cohort tuple (or one mapping, broadcast);
    ``outs`` is a per-cohort tuple of ``BatchOut`` (tenant axis leading);
    ``edges`` is the round's valid-edge count summed INSIDE the launch —
    a device scalar the caller can keep pending, so steady-state serving
    never blocks on a D2H sync to meter throughput.
    """

    def __init__(self, parts, *, donate_state: bool = False,
                 in_shardings=None, out_shardings=None, tenant_map=None,
                 obs=None):
        """``parts``: sequence of ``(pipeline, aux, rows)`` — one entry per
        cohort, ``rows`` its stacked-table capacity. ``donate_state``
        donates the per-cohort state tuple (resident tables updated in
        place); shardings pin mesh placements exactly as ``batched_step``.
        ``obs`` (an ``obs.MetricsRegistry``) mirrors ``traces``/``calls``
        into the ``compile.round_traces``/``compile.round_calls`` gauges
        so ``compile_counters`` reads one lock-consistent snapshot; the
        gauges keep the current-launch semantics (they reset with every
        fresh layout).
        """
        self.parts = tuple((p, a, int(r)) for p, a, r in parts)
        segments, lanes, lo = [], [], 0
        for pipe, _aux, rows in self.parts:
            segments.append((lo, lo + rows))
            lanes.extend([pipe.stages.variant_id] * rows)
            lo += rows
        self.segments = tuple(segments)
        self.rows = lo
        #: static per-row lane table of the super-batch (introspection).
        self.lane_ids = np.asarray(lanes, np.int32)
        #: number of compiled executions dispatched through this round
        #: launch (the serving tests' one-launch-per-round guard).
        self.calls = 0
        #: number of TRACES of the round body — one per compiled
        #: executable (jit traces exactly on cache miss), i.e. the
        #: compile counter the live-admission zero-recompile guard reads.
        self.traces = 0
        self._g_traces = self._g_calls = None
        if obs is not None:
            self._g_traces = obs.gauge("compile.round_traces")
            self._g_calls = obs.gauge("compile.round_calls")
            self._g_traces.set(0)        # a fresh layout starts at zero
            self._g_calls.set(0)

        steps = [(pipe.step, aux) for pipe, aux, _rows in self.parts]
        segs = self.segments

        # ``widths`` (static): each segment's padded batch width for this
        # round — the cohort's max submitted batch size, exactly the B the
        # per-cohort launch would compile for. Slicing every segment to
        # its own width (rather than running all at the super-batch's
        # global width) matters for the BITWISE contract: XLA's lowering
        # of the embedding math is shape-dependent, so the same real rows
        # under a different padded width can differ in the last ulp. With
        # per-segment widths the compiled segment programs are
        # shape-identical to the per-cohort launches, and jit caches one
        # executable per widths vector — the same recompile behavior the
        # per-cohort dispatch has per cohort.
        def round_fn(params, states, batch, ef, nf, widths):
            self.traces += 1          # trace time == compile time, not per call
            if self._g_traces is not None:
                self._g_traces.set(self.traces)
            outs = []
            for (lo, hi), (step, aux), p, state, w in zip(segs, steps,
                                                          params, states,
                                                          widths):
                seg = tuple(x[lo:hi, :w] for x in batch)

                def one(pp, s, b, e, n, _step=step, _aux=aux):
                    return _step(pp, _aux, s, b, e, n)

                vstep = jax.vmap(one, in_axes=(None, 0, 0, None, None))
                if tenant_map is not None:
                    vstep = tenant_map(vstep)
                outs.append(vstep(p, state, seg, ef, nf))
            return tuple(outs), jnp.sum(batch[4])

        kw = {}
        if in_shardings is not None:
            kw["in_shardings"] = in_shardings
        if out_shardings is not None:
            kw["out_shardings"] = out_shardings
        if donate_state:
            kw["donate_argnums"] = (1,)
        self._fn = jax.jit(round_fn, static_argnums=(5,), **kw)

    def __call__(self, params, states: tuple, superbatch: tuple,
                 edge_feats, node_feats=None, *, widths: tuple | None = None):
        if widths is None:
            widths = (superbatch[0].shape[1],) * len(self.parts)
        if isinstance(params, Mapping):      # shared-params fleet: broadcast
            params = (params,) * len(self.parts)
        self.calls += 1
        if self._g_calls is not None:
            self._g_calls.set(self.calls)
        return self._fn(params, states, superbatch, edge_feats, node_feats,
                        tuple(int(w) for w in widths))

    def lower(self, params: tuple, states: tuple, superbatch: tuple,
              edge_feats, node_feats=None, *, widths: tuple):
        """The round for these operands, lowered and not run (a
        ``jax.stages.Lowered``: ``.compile().as_text()`` shows what the
        chip executes)."""
        return self._fn.lower(params, states, superbatch, edge_feats,
                              node_feats, tuple(int(w) for w in widths))


@functools.lru_cache(maxsize=64)
def _cached_pipeline(cfg: tgn.TGNConfig, tier: str) -> TGNPipeline:
    return TGNPipeline(cfg, tier)


def build_pipeline(spec, use_kernels=False, **dims) -> TGNPipeline:
    """Build (or fetch the cached) pipeline for a variant.

    ``spec`` may be a TGNConfig (used as-is; ``dims`` must be empty) or any
    string/VariantSpec accepted by ``resolve_variant`` — then ``dims``
    supplies the TGNConfig table/feature fields. ``use_kernels`` selects
    the kernel tier (``stages.KERNEL_TIERS``: ``"ref"``/``"staged"``/
    ``"fused"``; legacy booleans accepted).
    """
    if isinstance(spec, tgn.TGNConfig):
        if dims:
            raise TypeError("dims are only valid with a variant spec, "
                            "not a full TGNConfig")
        cfg = spec
    else:
        cfg = variant_config(spec, **dims)
    # cache on the RESOLVED tier: "fused" on an uncovered variant is the
    # same program as "staged", so both requests share one pipeline
    return _cached_pipeline(cfg, stages.resolved_tier(cfg, use_kernels))
