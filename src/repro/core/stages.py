"""Algorithm 1 decomposed into pluggable stage interfaces.

Every Table-II variant (teacher included) is one composition of five stages;
``core/pipeline.py`` holds the registry and the composing ``TGNPipeline``:

  MemoryUpdater  (MUU)    consume cached mail -> updated memory rows.
                          cosine | LUT-reference | LUT-Pallas backends.
  NeighborSampler         read the ring buffer and produce the Neighborhood
                          the aggregator consumes. Two dataflows:
                            * fetch-all        (vanilla attention needs the
                              full m_r rows of memory/edge features)
                            * prune-then-fetch (selection from timestamps/ids
                              ONLY -> top-k -> gather just k rows; the HBM
                              saving the paper measures, §III-B)
                          Prune-then-fetch selection is a pluggable policy
                          (``SAMPLERS``): "recent" (SAT top-k, the paper),
                          "uniform", or time-decayed "reservoir" — both
                          randomized policies use a stateless hash so
                          serving stays deterministic and vmap-batchable.
  Aggregator     (EU)     vanilla attention | SAT reference | SAT-Pallas.
  Committer               chronological last-write-wins commit of memory and
                          cached mail (§IV-B). Winners are computed ONCE per
                          batch and shared by both commits.
  (insert)                neighbor ring-buffer FIFO insertion stays in
                          core/mailbox.py — it is parameter-free and common
                          to every variant.

Stages are pure closures built from a frozen ``TGNConfig``; per-call inputs
are ``(params, aux, ...)`` where ``aux = prepare(params)`` carries every
derived table (folded LUT rows, lane-packed Pallas parameters). Training
paths recompute ``aux`` inside the traced step so gradients flow through the
folds; the serving engine computes it once at session construction.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import attention as attn_mod
from repro.core import mailbox, memory, pruning, time_encode as te
from repro.core import updater


#: Kernel-backend tiers. ``use_kernels`` everywhere accepts a tier name or
#: the legacy booleans (False -> "ref", True -> "staged"):
#:   ref     pure-jnp stage references (the numerics oracle)
#:   staged  one Pallas kernel per unit (LUT encode, GRU, SAT aggregate) —
#:           stage boundaries still materialize HBM intermediates
#:   fused   the single-pass step kernel (kernels/fused_step.py): scalar-
#:           prefetched winner gather + EU + MUU in ONE launch, no
#:           inter-kernel intermediates (paper §IV, Fig. 4)
KERNEL_TIERS = ("ref", "staged", "fused")


def kernel_tier(use_kernels) -> str:
    """Normalize a ``use_kernels`` value (bool-like or tier name) to a
    tier: any falsy value is ``"ref"``, any truthy non-string (True, 1,
    np.True_) is ``"staged"``, strings must name a tier."""
    if isinstance(use_kernels, str):
        if use_kernels in KERNEL_TIERS:
            return use_kernels
        raise ValueError(f"unknown kernel tier {use_kernels!r}; pass a "
                         f"bool or one of {KERNEL_TIERS}")
    return "staged" if use_kernels else "ref"


def fused_supported(cfg) -> bool:
    """The fused single-pass kernel covers the co-designed student tail:
    SAT attention + LUT encoder (any prune budget / sampler backend),
    without static node features (the paper's Wikipedia/Reddit setting —
    f_feat > 0 would add a feature projection the kernel does not carry)."""
    return (cfg.attention == "sat" and cfg.encoder == "lut"
            and cfg.f_feat == 0)


def resolved_tier(cfg, use_kernels) -> str:
    """The tier that actually runs for ``cfg``: requesting ``"fused"`` on a
    variant outside the fused kernel's coverage silently degrades to the
    staged tier, mirroring how staged kernels degrade to references."""
    tier = kernel_tier(use_kernels)
    if tier == "fused" and not fused_supported(cfg):
        return "staged"
    return tier


def edge_rows(edge_feats: jax.Array, eids: jax.Array,
              f_edge: int) -> jax.Array:
    """Rows ``eids`` of the edge-feature table, native ``(..., f_edge)``:
    the table is either native ``(E, f_edge)`` or in the kernels' row
    layout (``kernels/ops.row_table``), which a serving session lays out
    once at construction and every tier reads."""
    x = edge_feats[eids]
    return x if edge_feats.ndim == 2 else x[..., 0, :f_edge]


def row_state(state):
    """``state`` with memory and mailbox in the row layout the fused
    kernel DMAs from (``kernels/ops.row_table``). The fused tier keeps its
    resident state this way, so no step re-lays a table."""
    from repro.kernels import ops as kops  # local: keep core importable
    return state._replace(memory=kops.row_table(state.memory),
                          mail=kops.row_table(state.mail))


def native_state(state, cfg):
    """Inverse of ``row_state``: the native ``(V, f)`` tables."""
    return state._replace(memory=state.memory[..., 0, :cfg.f_mem],
                          mail=state.mail[..., 0, :cfg.gru.f_mail_raw])


class Neighborhood(NamedTuple):
    """What a sampler hands the aggregator.

    ``s_nbr``/``e_nbr``/``dt``/``valid`` cover the FETCHED slots (k of them
    under prune-then-fetch, m_r otherwise). ``logits`` are the SAT scores of
    the fetched slots (None for the vanilla sampler, which scores inside the
    aggregator). ``full_*`` always span all m_r ring-buffer slots — the
    distillation views (Eq. 17 masking) regardless of pruning.
    """
    s_nbr: jax.Array            # (2B, k, f_mem) masked neighbor memory
    e_nbr: jax.Array            # (2B, k, f_edge) masked edge features
    dt: jax.Array               # (2B, k) time deltas of fetched slots
    valid: jax.Array            # (2B, k) fetched-slot validity
    logits: jax.Array | None    # (2B, k) SAT logits of fetched slots
    full_logits: jax.Array      # (2B, m_r) pre-softmax scores (distill)
    full_valid: jax.Array       # (2B, m_r) ring-buffer validity
    full_dt: jax.Array          # (2B, m_r) time deltas of every slot


class Selection(NamedTuple):
    """Prune-then-fetch METADATA — everything the selection policy decides
    from timestamps/ids alone, before any memory/feature gather. The
    staged sampler turns this into a ``Neighborhood`` by gathering the k
    winners' rows; the fused tier hands it (scalar-prefetched) straight to
    the single-pass kernel, which DMAs the rows itself.
    """
    ids: jax.Array              # (2B, k) int32 winner vertex ids
    eids: jax.Array             # (2B, k) int32 winner edge-feature rows
    dt: jax.Array               # (2B, k) winner time deltas
    logits: jax.Array           # (2B, k) SAT logits (NEG_INF where invalid)
    valid: jax.Array            # (2B, k) bool winner validity
    full_logits: jax.Array      # (2B, m_r) pre-softmax scores (distill)
    full_valid: jax.Array       # (2B, m_r) ring-buffer validity
    full_dt: jax.Array          # (2B, m_r) time deltas of every slot


class StageBundle(NamedTuple):
    """The resolved stage stack for one variant (+ backend choice)."""
    memory_updater: object      # (params, aux, state, vids) -> (s_upd, lu_upd)
    sampler: object             # (params, aux, state, ef, vids, t) -> Neighborhood
    aggregator: object          # (params, aux, nb, s_self, f_self) -> (h, logits)
    committer: object           # LastWriteWinsCommitter
    names: dict                 # stage-name -> backend label (introspection)
    variant_id: int             # lane id of this stage PROGRAM (variant_lane)
    fused: object = None        # fused tier only: the one-launch step body


#: Process-wide lane registry: every distinct resolved stage *program* (the
#: knobs that change which code runs inside ``TGNPipeline.step``, not the
#: table dims) gets a small stable integer id. The coalesced cross-cohort
#: round dispatcher (``pipeline.CoalescedRound``) uses these ids as its
#: static lane table: each row of the fused super-batch carries the
#: variant_id of the stage stack that must advance it.
_VARIANT_LANES: dict[tuple, int] = {}


def variant_lane(cfg, use_kernels=False) -> int:
    """The lane id of ``cfg``'s resolved stage program.

    Two configs share a lane iff ``build_stages`` would resolve them to the
    same stage code path: attention/encoder/pruning/sampler (tau included
    for the reservoir — it is baked into the sampler closure), plus the
    RESOLVED kernel tier (a variant the fused kernel cannot cover resolves
    to its staged lane) and the ring width the prune clamp sees.
    """
    key = (cfg.attention, cfg.encoder, cfg.prune_k, cfg.sampler,
           float(cfg.reservoir_tau) if cfg.sampler == "reservoir" else None,
           resolved_tier(cfg, use_kernels), cfg.m_r)
    return _VARIANT_LANES.setdefault(key, len(_VARIANT_LANES))


# ---------------------------------------------------------------------------
# aux preparation: folded LUT rows + lane-packed kernel parameters (§III-C)
# ---------------------------------------------------------------------------


def make_prepare(cfg, use_kernels=False):
    """Build ``prepare(params) -> aux`` for ``cfg`` (a TGNConfig).

    aux carries every parameter-derived table the resolved stage backends
    need:
      folded_gru / folded_attn   LUT tables pre-multiplied through the time
                                 rows of W_i / W_v (te.fold_projection)
      packed_gru / packed_lut_gru / packed_sat
                                 lane-aligned Pallas parameter layouts
                                 (kernels/ops.py pad_* helpers) — staged and
                                 fused tiers (the fused tier's ``embed``
                                 path still runs the staged backends)
      packed_fused               the single-pass kernel's parameter pack
                                 (kernels/ops.py pad_fused_params) — fused
                                 tier only
    Cheap jnp ops — safe to trace inside a training step (gradients flow
    through the folds) or run once at engine construction.
    """
    tier = resolved_tier(cfg, use_kernels)

    def prepare(params: dict) -> dict:
        aux = {}
        if cfg.encoder != "lut":
            return aux
        gcfg = cfg.gru
        gru_p = params["gru"]
        folded_gru = te.fold_projection(params["time"],
                                        gru_p["w_i"][gcfg.f_mail_raw:])
        aux["folded_gru"] = folded_gru
        folded_attn = None
        if cfg.attention == "sat":
            attn_p = params["attn"]
            dkv = cfg.f_mem + cfg.f_edge
            folded_attn = te.fold_projection(params["time"],
                                             attn_p["w_v"][dkv:])
            aux["folded_attn"] = folded_attn
        if tier == "ref":
            return aux
        from repro.kernels import ops as kops  # local: keep core importable
        aux["packed_gru"] = kops.pad_gru_params(
            {"w_i": gru_p["w_i"][:gcfg.f_mail_raw], "w_h": gru_p["w_h"],
             "b_i": gru_p["b_i"], "b_h": gru_p["b_h"]},
            gcfg.f_mail_raw, cfg.f_mem)
        aux["packed_lut_gru"] = kops.pad_lut_params(
            folded_gru["boundaries"], folded_gru["table"])
        if folded_attn is not None:
            aux["packed_sat"] = kops.pad_sat_params(
                attn_p["w_v"][:dkv], attn_p["b_v"],
                folded_attn["boundaries"], folded_attn["table"])
        if tier == "fused":
            aux["packed_fused"] = kops.pad_fused_params(
                gru_p, attn_p, folded_gru, folded_attn,
                gcfg.f_mail_raw, cfg.f_mem, cfg.f_edge)
        return aux

    return prepare


# ---------------------------------------------------------------------------
# MemoryUpdater (MUU)
# ---------------------------------------------------------------------------


def make_memory_updater(cfg, use_kernels: bool):
    """UPDT: consume cached messages for the involved vertex instances.

    Returns ``(muu, backend_name)``; ``muu(params, aux, state, vids)`` maps
    the cached mail of ``vids`` to updated (memory, last_update) rows.
    Vertices without valid mail keep their previous rows. The Pallas backend
    exists for the LUT encoder only; other combinations fall back to the
    jnp reference.
    """
    gcfg = cfg.gru

    if cfg.encoder == "lut" and use_kernels:
        from repro.kernels import ops as kops

        def muu(params, aux, state, vids):
            mail_raw = state.mail[vids]
            mail_ts = state.mail_ts[vids]
            mail_valid = state.mail_valid[vids]
            s_prev = state.memory[vids]
            lu_prev = state.last_update[vids]
            # LUT row fetch (Pallas) -> fused GRU (Pallas): the folded time
            # rows enter the kernel as an additive input-gate term.
            dt_mail = mail_ts - lu_prev
            time_rows = kops.lut_encode(dt_mail, aux["packed_lut_gru"])
            s_new = kops.gru_cell(mail_raw, s_prev, aux["packed_gru"],
                                  extra=time_rows)
            s_upd = jnp.where(mail_valid[:, None], s_new, s_prev)
            lu_upd = jnp.where(mail_valid, mail_ts, lu_prev)
            return s_upd, lu_upd

        return muu, "gru:lut-pallas"

    def muu(params, aux, state, vids):
        return memory.update_memory(
            params["gru"], params["time"], gcfg,
            state.mail[vids], state.mail_ts[vids], state.mail_valid[vids],
            state.memory[vids], state.last_update[vids],
            encoder=cfg.encoder, lut_folded=aux.get("folded_gru"))

    return muu, f"gru:{cfg.encoder}-ref"


# ---------------------------------------------------------------------------
# NeighborSampler / Pruner
# ---------------------------------------------------------------------------

#: Registered sampler backends (the selection policy of prune-then-fetch).
#:   recent     paper behavior — SAT top-k over the FIFO ring buffer
#:   uniform    k valid slots uniformly at random (stateless hash RNG)
#:   reservoir  time-decayed weighted reservoir (Efraimidis–Spirakis keys
#:              with weight exp(-dt/tau)) — recency-biased but randomized
SAMPLERS = ("recent", "uniform", "reservoir")


def _stateless_uniform(eid: jax.Array, vids: jax.Array,
                       t_query: jax.Array) -> jax.Array:
    """Deterministic pseudo-uniform draws in (0, 1) per (vertex, slot).

    A jit/vmap-safe integer hash of (edge id, queried vertex, query-time
    bits) — no PRNG key threading, so multi-tenant vmapped serving and a
    lone engine sample IDENTICAL neighborhoods for identical inputs (the
    bitwise-equivalence guarantee tests/test_session.py checks).

    eid: (B, m_r) int32; vids: (B,) int; t_query: (B,) float32.
    """
    h = eid.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
    h = h ^ (vids.astype(jnp.uint32)[:, None] * jnp.uint32(0x85EBCA77))
    tb = jax.lax.bitcast_convert_type(t_query.astype(jnp.float32),
                                      jnp.uint32)
    h = h ^ (tb[:, None] * jnp.uint32(0xC2B2AE3D))
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x2C1B3C6D)
    h = h ^ (h >> 12)
    h = h * jnp.uint32(0x297A2D39)
    h = h ^ (h >> 15)
    # 24 mantissa-safe bits -> (0, 1); +2^-25 keeps log(u) finite
    return ((h >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
            + jnp.float32(2.0 ** -25))


def make_sampler(cfg):
    """Returns ``(sampler, backend_name)``.

    ``sampler(params, aux, state, edge_feats, vids, t_query) -> Neighborhood``
    reads the ring buffer for ``vids`` at query times ``t_query``. The
    ``cfg.sampler`` backend picks WHICH k slots are fetched (``SAMPLERS``);
    aggregation weights always come from the SAT logits of the fetched
    slots, so the prune-then-fetch HBM saving is preserved: every policy
    decides from timestamps/ids ONLY, before any memory/feature gather.
    """
    if cfg.sampler not in SAMPLERS:
        raise ValueError(f"unknown sampler backend {cfg.sampler!r}; "
                         f"registered backends: {SAMPLERS}")
    if cfg.attention == "vanilla":
        if cfg.sampler != "recent":
            raise ValueError(
                "alternative sampler backends (uniform/reservoir) require "
                "SAT attention: vanilla fetch-all consumes every ring-buffer "
                f"slot, so there is no selection to randomize — got "
                f"sampler={cfg.sampler!r}")
        # fetch-all: vanilla attention scores depend on neighbor memory, so
        # every m_r row must be gathered before scoring.
        def sampler(params, aux, state, edge_feats, vids, t_query):
            nbr_ids, nbr_ts, nbr_eid, valid = mailbox.gather_neighbors(
                state, vids)
            dt = jnp.maximum(t_query[:, None] - nbr_ts, 0.0) * valid
            s_nbr = state.memory[nbr_ids] * valid[..., None]
            e_nbr = edge_rows(edge_feats, nbr_eid, cfg.f_edge) \
                * valid[..., None]
            return Neighborhood(s_nbr=s_nbr, e_nbr=e_nbr, dt=dt, valid=valid,
                                logits=None, full_logits=dt * 0.0,
                                full_valid=valid, full_dt=dt)

        return sampler, "sampler:fetch-all"

    select, name = make_selector(cfg)

    # prune-then-fetch: selection is metadata-only (make_selector); here we
    # fetch ONLY the winners' rows (the point of the co-design).
    def sampler(params, aux, state, edge_feats, vids, t_query):
        sel = select(params, aux, state, vids, t_query)
        s_nbr = state.memory[sel.ids] * sel.valid[..., None]
        e_nbr = edge_rows(edge_feats, sel.eids, cfg.f_edge) \
            * sel.valid[..., None]
        return Neighborhood(s_nbr=s_nbr, e_nbr=e_nbr, dt=sel.dt,
                            valid=sel.valid, logits=sel.logits,
                            full_logits=sel.full_logits,
                            full_valid=sel.full_valid, full_dt=sel.full_dt)

    return sampler, name


def make_selector(cfg):
    """Returns ``(select, backend_name)`` — the metadata half of
    prune-then-fetch for the SAT variants.

    ``select(params, aux, state, vids, t_query) -> Selection`` decides the
    k winners from the ring buffer's timestamps/ids ONLY, so top-k
    selection runs BEFORE any memory/edge-feature gather and HBM traffic
    scales with k, not m_r (the paper's 67% MEM saving). "recent" ranks by
    SAT logit (the paper's pruner); "uniform"/"reservoir" rank by a
    stateless-hash priority instead. The staged sampler gathers the
    winners' rows from this; the fused kernel scalar-prefetches it.
    """
    k = cfg.prune_k if cfg.prune_k is not None else cfg.m_r
    k = min(k, cfg.m_r)
    policy = cfg.sampler
    tau = float(cfg.reservoir_tau)

    def select(params, aux, state, vids, t_query):
        nbr_ids, nbr_ts, nbr_eid, valid = mailbox.gather_neighbors(
            state, vids)
        dt = jnp.maximum(t_query[:, None] - nbr_ts, 0.0) * valid
        logits = attn_mod.sat_logits(params["attn"], dt)      # ts ONLY
        if policy != "recent":
            u = _stateless_uniform(nbr_eid, vids, t_query)
            if policy == "uniform":
                prio = u
            else:
                # Efraimidis–Spirakis weighted reservoir: key = u^(1/w) with
                # w = exp(-dt/tau); rank by log key = log(u) * exp(dt/tau).
                prio = jnp.log(u) * jnp.exp(jnp.minimum(dt / tau, 50.0))
            idx, _, sel_valid = pruning.topk_select(prio, valid, k)
            sel_ids = jnp.take_along_axis(nbr_ids, idx, axis=1)
            sel_eid = jnp.take_along_axis(nbr_eid, idx, axis=1)
            sel_dt = jnp.take_along_axis(dt, idx, axis=1)
            sel_logits = jnp.where(sel_valid,
                                   jnp.take_along_axis(logits, idx, axis=1),
                                   pruning.NEG_INF)
        elif k < cfg.m_r:
            idx, sel_logits, sel_valid = pruning.topk_select(logits, valid, k)
            sel_ids = jnp.take_along_axis(nbr_ids, idx, axis=1)
            sel_eid = jnp.take_along_axis(nbr_eid, idx, axis=1)
            sel_dt = jnp.take_along_axis(dt, idx, axis=1)
        else:
            sel_ids, sel_eid, sel_dt = nbr_ids, nbr_eid, dt
            sel_logits, sel_valid = logits, valid
        return Selection(ids=sel_ids, eids=sel_eid, dt=sel_dt,
                         logits=sel_logits, valid=sel_valid,
                         full_logits=logits, full_valid=valid, full_dt=dt)

    if policy == "uniform":
        name = f"sampler:uniform(k={k})"
    elif policy == "reservoir":
        name = f"sampler:reservoir(k={k},tau={tau:g})"
    else:
        name = (f"sampler:prune-then-fetch(k={k})" if k < cfg.m_r
                else "sampler:score-all")
    return select, name


# ---------------------------------------------------------------------------
# Aggregator (EU)
# ---------------------------------------------------------------------------


def make_aggregator(cfg, use_kernels: bool):
    """Returns ``(aggregator, backend_name)``.

    ``aggregator(params, aux, nb, s_self, f_self) -> (h, distill_logits)``
    consumes a Neighborhood and the self rows. The Pallas backend covers the
    SAT+LUT student tail; everything else runs the jnp reference.
    """
    acfg = cfg.attn

    if cfg.attention == "vanilla":
        def aggregator(params, aux, nb, s_self, f_self):
            return attn_mod.vanilla_attention(
                params["attn"], acfg, params["time"],
                s_self, f_self, nb.s_nbr, nb.e_nbr, nb.dt, nb.valid)

        return aggregator, "attn:vanilla-ref"

    dkv = cfg.f_mem + cfg.f_edge

    if cfg.encoder == "lut" and use_kernels:
        from repro.kernels import ops as kops

        def aggregator(params, aux, nb, s_self, f_self):
            # fused: logits -> masked softmax -> V-projection+LUT -> sum
            kv = jnp.concatenate([nb.s_nbr, nb.e_nbr], axis=-1)
            agg = kops.sat_aggregate(kv, nb.dt, nb.logits, nb.valid,
                                     aux["packed_sat"])
            fp = attn_mod.feat_proj(params["attn"]["feat"], s_self, f_self)
            h = (jnp.concatenate([fp, agg], axis=-1)
                 @ params["attn"]["w_out"] + params["attn"]["b_out"])
            return h, nb.full_logits

        return aggregator, "attn:sat-lut-pallas"

    def aggregator(params, aux, nb, s_self, f_self):
        attn_p = params["attn"]
        attnw = pruning.masked_softmax(nb.logits, nb.valid)
        if cfg.encoder == "lut":
            folded = aux.get("folded_attn")
            if folded is None:
                folded = te.fold_projection(params["time"],
                                            attn_p["w_v"][dkv:])
            v = (jnp.concatenate([nb.s_nbr, nb.e_nbr], axis=-1)
                 @ attn_p["w_v"][:dkv]
                 + te.lut_encode(folded, nb.dt) + attn_p["b_v"])
        else:
            phi = te.cosine_encode(params["time"], nb.dt)
            kv_in = jnp.concatenate([nb.s_nbr, nb.e_nbr, phi], axis=-1)
            v = kv_in @ attn_p["w_v"] + attn_p["b_v"]
        agg = jnp.einsum("bn,bnd->bd", attnw, v)
        fp = attn_mod.feat_proj(attn_p["feat"], s_self, f_self)
        h = (jnp.concatenate([fp, agg], axis=-1)
             @ attn_p["w_out"] + attn_p["b_out"])
        return h, nb.full_logits

    return aggregator, f"attn:sat-{cfg.encoder}-ref"


# ---------------------------------------------------------------------------
# Committer — chronological last-write-wins (§IV-B)
# ---------------------------------------------------------------------------


class LastWriteWinsCommitter:
    """Chronological Updater semantics on SIMD: per batch, exactly the
    chronologically-last valid update of each vertex survives. The winner
    mask is computed ONCE per batch and shared by the memory commit and the
    mail commit (both race over the same (vids, vvalid) layout).
    """

    def winners(self, vids: jax.Array, vvalid: jax.Array,
                B: int) -> jax.Array:
        return updater.last_write_wins(vids, vvalid,
                                       updater.interleave_order(B))

    def commit_memory(self, state, vids, winners, s_upd, lu_upd):
        """Commit updated memory rows; consuming mail invalidates it."""
        mem_t = updater.commit(state.memory, vids, s_upd, winners)
        lu_t = updater.commit_scalar(state.last_update, vids, lu_upd,
                                     winners)
        mv_t = updater.commit_scalar(
            state.mail_valid, vids,
            jnp.zeros(vids.shape, state.mail_valid.dtype), winners)
        return state._replace(memory=mem_t, last_update=lu_t,
                              mail_valid=mv_t)

    def commit_mail(self, state, vids, winners, new_mail, t_inst):
        """Cache new messages (Most-Recent aggregator == LWW commit)."""
        mail_t = updater.commit(state.mail, vids, new_mail, winners)
        mts_t = updater.commit_scalar(state.mail_ts, vids, t_inst, winners)
        mvv_t = updater.commit_scalar(
            state.mail_valid, vids,
            jnp.ones(vids.shape, state.mail_valid.dtype), winners)
        return state._replace(mail=mail_t, mail_ts=mts_t, mail_valid=mvv_t)


# ---------------------------------------------------------------------------
# Fused tier: the single-pass step body (§IV, Fig. 4)
# ---------------------------------------------------------------------------


def make_fused_step(cfg):
    """Build the fused-tier step body: prune metadata -> ONE kernel launch
    (winner gather + EU + MUU) -> state commits.

    The returned closure replaces the staged ``memory_updater -> commit ->
    sampler -> aggregator`` chain inside ``TGNPipeline.step``: selection
    stays a metadata computation (timestamps/ids only, the prune-then-fetch
    contract), the kernel DMAs only the winners' rows, and the committed
    memory view inside the batch is resolved through the kernel's phase-0
    scratch instead of a scatter/gather HBM round-trip. The mail build and
    the state commits — genuine state writes the paper's design also pays —
    stay in XLA after the launch.

    The kernel reads memory, mailbox and edge features in the row layout
    (``row_state``, ``kernels/ops.row_table``), so the state and the edge
    table must arrive in it — ``TGNPipeline.resident`` and a serving
    session's edge table: laid out once, never per step.
    """
    from repro.kernels import ops as kops  # local: keep core importable
    from repro.core import tgn             # local: BatchOut (no cycle)

    select, _ = make_selector(cfg)
    committer = LastWriteWinsCommitter()
    V = cfg.n_nodes

    def datapath(params, aux, state, edge_feats, vids, t_inst, winners):
        """Metadata + the one launch. This function must never materialize
        a neighbor row itself: only ids/timestamps/validity leave XLA
        (tools/session_lint.py AST-guards it against jnp.concatenate and
        memory/mail/edge-feature gathers creeping back in)."""
        sel = select(params, aux, state, vids, t_inst)
        mail_ts = state.mail_ts[vids]
        lu_prev = state.last_update[vids]
        mail_ok = state.mail_valid[vids]
        # winner-row redirect table (ids only): hit[r, j] >= 0 names the
        # batch row whose phase-0 GRU output IS the committed memory of
        # winner (r, j) — the kernel reads it from VMEM scratch, giving the
        # exact post-commit view the staged path gets from its scatter.
        R = vids.shape[0]
        win_rows = jnp.full((V + 1,), -1, jnp.int32).at[
            jnp.where(winners, vids, V)].set(
                jnp.arange(R, dtype=jnp.int32))
        hit = win_rows[sel.ids]
        h, s_upd = kops.fused_step(
            vids, sel.ids, sel.eids, hit, mail_ts - lu_prev, mail_ok,
            sel.dt, sel.logits, sel.valid, state.memory, state.mail,
            edge_feats, aux["packed_fused"])
        lu_upd = jnp.where(mail_ok, mail_ts, lu_prev)
        return sel, h, s_upd, lu_upd

    def fused(params, aux, state, batch, vids, t_inst, vvalid, edge_feats,
              node_feats):
        src, dst, eid, ts, valid = batch
        B = src.shape[0]
        if state.memory.ndim != 3 or edge_feats.ndim != 3:
            raise ValueError("the fused tier steps row-layout tables: pass "
                             "pipeline.resident(state) and "
                             "kernels.ops.row_table(edge_feats)")
        winners = committer.winners(vids, vvalid, B)
        sel, h, s_upd, lu_upd = datapath(params, aux, state, edge_feats,
                                         vids, t_inst, winners)
        state = committer.commit_memory(state, vids, winners,
                                        kops.row_table(s_upd), lu_upd)
        # mail build: committed memory of a VALID row r is exactly
        # s_upd[r] (duplicates of a vertex compute identical updates and
        # the LWW commit picks one), so the staged path's post-commit
        # memory gather is unnecessary; losers' mail is dropped by the
        # commit anyway.
        fe = edge_rows(edge_feats, eid, cfg.f_edge)
        mail_src = memory.build_mail_raw(s_upd[:B], s_upd[B:], fe)
        mail_dst = memory.build_mail_raw(s_upd[B:], s_upd[:B], fe)
        new_mail = jnp.concatenate([mail_src, mail_dst], axis=0)
        state = committer.commit_mail(state, vids, winners,
                                      kops.row_table(new_mail), t_inst)
        state = mailbox.insert_neighbors(state, src, dst, eid, ts, valid)
        return tgn.BatchOut(state=state, emb_src=h[:B], emb_dst=h[B:],
                            attn_logits=sel.full_logits,
                            nbr_valid=sel.full_valid, nbr_dt=sel.full_dt)

    return fused


def build_stages(cfg, use_kernels=False) -> StageBundle:
    """Resolve the stage stack for ``cfg`` (a TGNConfig).

    ``use_kernels`` picks the tier (see ``KERNEL_TIERS``; booleans
    accepted). Pallas kernel backends exist for the LUT encoder paths
    (MUU) and the SAT+LUT aggregation tail; any stage without a kernel
    backend silently uses its jnp reference, so every variant — teacher
    included — builds and runs. The fused tier additionally carries the
    single-pass step body; its per-stage backends are the STAGED ones
    (``embed`` and distillation views still run stage-at-a-time), and
    variants outside ``fused_supported`` resolve to their staged program.
    """
    if cfg.attention == "vanilla" and cfg.encoder != "cosine":
        raise ValueError("vanilla attention requires the cosine encoder "
                         "(its K/Q/V inputs consume the cosine encoding "
                         "directly; LUT is a SAT-path optimization)")
    tier = resolved_tier(cfg, use_kernels)
    staged = tier != "ref"
    muu, muu_name = make_memory_updater(cfg, staged)
    sampler, sampler_name = make_sampler(cfg)
    aggregator, agg_name = make_aggregator(cfg, staged)
    names = {"memory_updater": muu_name, "sampler": sampler_name,
             "aggregator": agg_name, "committer": "lww-chronological"}
    fused = None
    if tier == "fused":
        fused = make_fused_step(cfg)
        names["fused_step"] = "step:single-pass-pallas"
    return StageBundle(
        memory_updater=muu, sampler=sampler, aggregator=aggregator,
        committer=LastWriteWinsCommitter(), names=names,
        variant_id=variant_lane(cfg, use_kernels), fused=fused)
