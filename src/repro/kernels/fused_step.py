"""Fused single-pass step kernel — the paper's §IV pipelined datapath on TPU.

The paper's hardware contribution (Fig. 4) is a *single-pass* datapath: a
prefetcher pulls ONLY the pruned winners' rows out of Graph Storage, and
sampler -> time-LUT -> attention (EU) -> memory update (MUU) stream through
on-chip buffers without ever round-tripping to off-chip memory. The staged
Pallas tier reproduces each unit as its own kernel, but every stage boundary
(the ``(B, k, Dkv)`` neighbor tensor, the kv concat, the LUT rows, the GRU
inputs) is a full HBM materialization XLA schedules between launches.

This kernel is the whole post-prune datapath in ONE ``pallas_call``:

  * the pruned winner indices (``sel_ids``/``sel_eid``) plus the involved
    vertex ids arrive as **scalar-prefetched** operands (SMEM) — metadata
    computed from timestamps/ids only, upstream, preserving the
    prune-then-fetch contract of §III-B;
  * the vertex memory / mailbox / edge-feature tables stay in HBM
    (``memory_space=ANY``) in the row layout of ``ops.row_table``
    (``(rows, 1, W_p)``, W_p a LANE multiple); per batch tile the kernel
    DMAs exactly the k winner rows (plus the tile's own mail/memory rows)
    into ``(n, 1, W_p)`` VMEM row buffers — the jax analogue of the
    paper's prefetcher. Both sides are row-addressable and lane-aligned,
    which is what the TPU's DMA engine accepts for a one-row copy;
  * phase 0 (MUU): mail rows through the fused LUT+GRU -> updated memory
    rows, written both to the ``s_upd`` output and to a persistent VMEM
    scratch that spans the whole batch;
  * phase 1 (EU): winner-row gather (neighbors updated by THIS batch are
    read back from the phase-0 scratch, not from stale HBM — the
    chronological-commit view the staged path gets from its scatter),
    split-matmul kv projection (no concat), folded-LUT time rows, masked
    softmax, FAM reduction and the output transform.

The TPU grid is sequential, so ``grid=(2, T)`` runs every phase-0 tile
before any phase-1 tile — exactly the MUU->commit->EU ordering of
Algorithm 1 — and the scratch carries the updated rows across grid steps.

VMEM working set per tile (fp32 words): the persistent updated-row buffer
``R_p x m_p`` plus gather buffers ``block_b x f_p`` (mail) and
``block_b*k x (m_p + e_p)`` (neighbors) plus the weights
(``f_p x 3m_p + m_p x 3m_p + m_p x d_p + e_p x d_p + 2 E x (3m_p|d_p)``).
For paper dims (B=256 -> R=512, k=4, f_mem=100, f_edge=172, E=128) that is
~2.1 MiB — comfortably inside one core's 16 MiB.

Per-row copies are issued through one DMA semaphore with an immediate
wait; a production kernel would rotate a semaphore array to keep several
row fetches in flight, which changes no numerics. The winner time deltas
arrive flattened to ``(R*k, 1)`` by the wrapper: no reshape inside the
kernel moves lanes into sublanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import NEG_INF
from repro.kernels.lut_time_encode import lut_rows


def _fused_kernel(  # scalar prefetch (SMEM)
                  vids_ref, sel_ids_ref, sel_eid_ref, hit_ref,
                  # grid-blocked VMEM operands
                  dt_mail_ref, mail_ok_ref, sel_dt_ref, logits_ref,
                  valid_ref,
                  # HBM-resident row tables (manual DMA)
                  mem_hbm, mail_hbm, ef_hbm,
                  # weights (VMEM, whole-array blocks)
                  w_i_ref, w_h_ref, b_i_ref, b_h_ref, gb_ref, gt_ref,
                  wv_mem_ref, wv_edge_ref, b_v_ref, sb_ref, st_ref,
                  w_self_ref, w_agg_ref, b_out_ref,
                  # outputs
                  h_ref, supd_ref,
                  # scratch
                  supd_all, mail_scr, self_scr, nbr_s, nbr_e, sem,
                  *, k: int, f_edge: int, n_entries: int, block_b: int):
    """One grid step of the two-phase single-pass datapath (see module
    docstring for the shapes)."""
    ph = pl.program_id(0)
    t = pl.program_id(1)
    bb = block_b
    nk = bb * k
    m_p = supd_all.shape[2]

    def copy(src, dst):
        cp = pltpu.make_async_copy(src, dst, sem)
        cp.start()
        cp.wait()

    @pl.when(ph == 0)
    def _muu():
        # --- prefetch: this tile's mail + pre-update memory rows ----------
        def fetch(i, _):
            v = vids_ref[t * bb + i]
            copy(mail_hbm.at[v], mail_scr.at[i])
            copy(mem_hbm.at[v], self_scr.at[i])
            return 0

        jax.lax.fori_loop(0, bb, fetch, 0)

        # --- fused LUT + GRU (gate blocks at m_p strides) -----------------
        mail = mail_scr[...].reshape(bb, mail_scr.shape[2])
        gi = jnp.dot(mail, w_i_ref[...], preferred_element_type=jnp.float32)
        gi = gi + b_i_ref[...]
        gi = gi + lut_rows(dt_mail_ref[...], gb_ref, gt_ref, n_entries)
        s_prev = self_scr[...].reshape(bb, m_p)
        gh = jnp.dot(s_prev, w_h_ref[...],
                     preferred_element_type=jnp.float32) + b_h_ref[...]
        r = jax.nn.sigmoid(gi[:, :m_p] + gh[:, :m_p])
        z = jax.nn.sigmoid(gi[:, m_p:2 * m_p] + gh[:, m_p:2 * m_p])
        n = jnp.tanh(gi[:, 2 * m_p:] + r * gh[:, 2 * m_p:])
        s_new = (1.0 - z) * n + z * s_prev
        s_upd = jnp.where(mail_ok_ref[...] > 0, s_new, s_prev)

        # persist for phase 1 (self rows AND same-batch neighbor overrides)
        supd_all[pl.ds(t * bb, bb)] = s_upd.reshape(bb, 1, m_p)
        supd_ref[...] = s_upd
        h_ref[...] = jnp.zeros_like(h_ref)

    @pl.when(ph == 1)
    def _eu():
        # --- prefetch: ONLY the k winners' memory/edge rows per vertex ----
        # Winners whose vertex was updated by THIS batch (hit >= 0) are
        # read back from the phase-0 scratch — the committed view — so the
        # kernel never needs the scatter/gather round-trip through HBM.
        def fetch(j, _):
            f = t * nk + j
            hit = hit_ref[f]

            @pl.when(hit >= 0)
            def _():
                copy(supd_all.at[hit], nbr_s.at[j])

            @pl.when(hit < 0)
            def _():
                copy(mem_hbm.at[sel_ids_ref[f]], nbr_s.at[j])

            if f_edge:
                copy(ef_hbm.at[sel_eid_ref[f]], nbr_e.at[j])
            return 0

        jax.lax.fori_loop(0, nk, fetch, 0)

        # --- kv projection WITHOUT the concat: two split matmuls ----------
        v = jnp.dot(nbr_s[...].reshape(nk, m_p), wv_mem_ref[...],
                    preferred_element_type=jnp.float32)
        if f_edge:
            v = v + jnp.dot(nbr_e[...].reshape(nk, nbr_e.shape[2]),
                            wv_edge_ref[...],
                            preferred_element_type=jnp.float32)
        v = v + lut_rows(sel_dt_ref[...], sb_ref, st_ref, n_entries)
        v = v + b_v_ref[...]
        d_p = v.shape[1]
        v = v.reshape(bb, k, d_p)

        # --- masked softmax over the k winners (Eq. 16) -------------------
        valid = valid_ref[...]
        logits = jnp.where(valid > 0, logits_ref[...], NEG_INF)
        mx = jnp.max(logits, axis=1, keepdims=True)
        e = jnp.exp(logits - mx) * valid
        zs = jnp.sum(e, axis=1, keepdims=True)
        attn = jnp.where(zs > 0, e / jnp.maximum(zs, 1e-30), 0.0)

        # --- FAM reduction + output transform (split, no concat) ---------
        agg = jnp.sum(attn[:, :, None] * v, axis=1)
        fp = supd_all[pl.ds(t * bb, bb)].reshape(bb, m_p)
        h = jnp.dot(fp, w_self_ref[...],
                    preferred_element_type=jnp.float32)
        h = h + jnp.dot(agg, w_agg_ref[...],
                        preferred_element_type=jnp.float32)
        h_ref[...] = h + b_out_ref[...]
        supd_ref[...] = fp


@functools.partial(jax.jit, static_argnames=("k", "f_edge", "block_b",
                                             "interpret"))
def fused_step_pallas(vids, sel_ids, sel_eid, hit, dt_mail, mail_ok,
                      sel_dt, sel_logits, sel_valid,
                      memory, mail, edge_feats,
                      w_i, w_h, b_i, b_h, g_bounds, g_table,
                      wv_mem, wv_edge, b_v, s_bounds, s_table,
                      w_self, w_agg, b_out,
                      *, k: int, f_edge: int, block_b: int,
                      interpret: bool = False):
    """One launch for the post-prune datapath of one batch.

    Scalar prefetch (int32): ``vids`` (R,), flat ``sel_ids``/``sel_eid``/
    ``hit`` (R*k,) — ``hit[f] >= 0`` redirects winner ``f`` to the phase-0
    updated row (its vertex was committed by this batch). Blocked operands:
    ``dt_mail``/``mail_ok`` (R, 1), ``sel_dt`` (R*k, 1),
    ``sel_logits``/``sel_valid`` (R, k). HBM row tables (``ops.row_table``
    layout, zero lane padding): ``memory`` (V, 1, m_p), ``mail``
    (V, 1, f_p), ``edge_feats`` (E_rows, 1, e_p). Weights are
    kernel-layout (lane-padded OUT dims, gate blocks at m_p strides; see
    ops.pad_fused_params).
    R must be a multiple of ``block_b``. Returns ``(h, s_upd)`` —
    (R, emb_p) embeddings and (R, m_p) updated memory rows.
    """
    R = vids.shape[0]
    assert R % block_b == 0, (R, block_b)
    assert sel_dt.shape == (R * k, 1), (sel_dt.shape, R, k)
    m_p = w_h.shape[0]
    d_p = wv_mem.shape[1]
    e_p = wv_edge.shape[0]
    emb_p = w_self.shape[1]
    E = g_table.shape[0]
    f_p = w_i.shape[0]
    T = R // block_b
    nk = block_b * k
    assert memory.shape[1:] == (1, m_p), (memory.shape, m_p)
    assert mail.shape[1:] == (1, f_p), (mail.shape, f_p)
    assert edge_feats.shape[1:] == (1, e_p), (edge_feats.shape, e_p)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(2, T),
        in_specs=[
            pl.BlockSpec((block_b, 1), lambda ph, t, *_: (t, 0)),
            pl.BlockSpec((block_b, 1), lambda ph, t, *_: (t, 0)),
            pl.BlockSpec((nk, 1), lambda ph, t, *_: (t, 0)),
            pl.BlockSpec((block_b, k), lambda ph, t, *_: (t, 0)),
            pl.BlockSpec((block_b, k), lambda ph, t, *_: (t, 0)),
            pl.BlockSpec(memory_space=pl.ANY),          # memory table
            pl.BlockSpec(memory_space=pl.ANY),          # mailbox table
            pl.BlockSpec(memory_space=pl.ANY),          # edge features
            pl.BlockSpec((f_p, 3 * m_p), lambda ph, t, *_: (0, 0)),
            pl.BlockSpec((m_p, 3 * m_p), lambda ph, t, *_: (0, 0)),
            pl.BlockSpec((1, 3 * m_p), lambda ph, t, *_: (0, 0)),
            pl.BlockSpec((1, 3 * m_p), lambda ph, t, *_: (0, 0)),
            pl.BlockSpec((1, E), lambda ph, t, *_: (0, 0)),
            pl.BlockSpec((E, 3 * m_p), lambda ph, t, *_: (0, 0)),
            pl.BlockSpec((m_p, d_p), lambda ph, t, *_: (0, 0)),
            pl.BlockSpec((e_p, d_p), lambda ph, t, *_: (0, 0)),
            pl.BlockSpec((1, d_p), lambda ph, t, *_: (0, 0)),
            pl.BlockSpec((1, E), lambda ph, t, *_: (0, 0)),
            pl.BlockSpec((E, d_p), lambda ph, t, *_: (0, 0)),
            pl.BlockSpec((m_p, emb_p), lambda ph, t, *_: (0, 0)),
            pl.BlockSpec((d_p, emb_p), lambda ph, t, *_: (0, 0)),
            pl.BlockSpec((1, emb_p), lambda ph, t, *_: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, emb_p), lambda ph, t, *_: (t, 0)),
            pl.BlockSpec((block_b, m_p), lambda ph, t, *_: (t, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, 1, m_p), jnp.float32),       # updated rows
            pltpu.VMEM((block_b, 1, f_p), jnp.float32),  # mail tile
            pltpu.VMEM((block_b, 1, m_p), jnp.float32),  # pre-update memory
            pltpu.VMEM((nk, 1, m_p), jnp.float32),      # winner memory rows
            pltpu.VMEM((nk, 1, e_p), jnp.float32),      # winner edge rows
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        functools.partial(_fused_kernel, k=k, f_edge=f_edge, n_entries=E,
                          block_b=block_b),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, emb_p), jnp.float32),
                   jax.ShapeDtypeStruct((R, m_p), jnp.float32)],
        interpret=interpret,
    )(vids, sel_ids, sel_eid, hit, dt_mail, mail_ok, sel_dt, sel_logits,
      sel_valid, memory, mail, edge_feats, w_i, w_h, b_i, b_h, g_bounds,
      g_table, wv_mem, wv_edge, b_v, s_bounds, s_table, w_self, w_agg,
      b_out)
