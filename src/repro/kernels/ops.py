"""Public jit'd entry points for the Pallas kernels.

These wrappers own all the padding/unpadding between the paper's native dims
(f_mem=100, f_edge=172, ...) and the LANE(128)-aligned shapes the kernels
require, pick interpret mode on the CPU backend (compiled Mosaic on a TPU,
an error anywhere else), and repack the core/ parameter layout (gate blocks
at f_mem strides) into the lane-aligned kernel layout (gate blocks at m_p
strides).

Tables the fused kernel reads row by row (vertex memory, mailbox, edge
features) use the **row layout** of ``row_table``: ``(rows, 1, W_p)`` with
``W_p`` a LANE multiple. The TPU lays such an array out with (1, 128) tiles,
so one row is one aligned DMA; a 2-D ``(rows, W)`` table is tiled (8, 128)
and the chip's compiler refuses a one-row slice of it.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import LANE, round_up
from repro.kernels.gru_cell import gru_cell_pallas
from repro.kernels.sat_aggregate import sat_aggregate_pallas
from repro.kernels.lut_time_encode import lut_encode_pallas
from repro.kernels.fused_step import fused_step_pallas

#: interpret-mode override: None = auto (interpret off-TPU); True/False
#: force it. The HLO byte-accounting benchmark traces with interpret
#: forced OFF so the kernels lower to opaque Mosaic custom-calls whose
#: operand/result bytes ARE the launch's HBM traffic.
_INTERPRET = {"override": None}


@contextlib.contextmanager
def force_interpret(mode: bool | None):
    """Force (or restore auto) interpret-mode selection for every kernel
    entry point while the context is active (trace-time switch)."""
    prev = _INTERPRET["override"]
    _INTERPRET["override"] = mode
    try:
        yield
    finally:
        _INTERPRET["override"] = prev


def _use_interpret() -> bool:
    """Interpret on the CPU backend, compile for a TPU, refuse anything
    else: a kernel that silently interprets on an accelerator would hide
    the device it was meant to run on."""
    if _INTERPRET["override"] is not None:
        return bool(_INTERPRET["override"])
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels run compiled on a TPU or "
                       f"interpreted on the CPU; backend {backend!r} is "
                       f"neither")


#: Trace-time kernel-launch counter: every public entry point below bumps
#: it when its pallas_call is staged into a trace, so
#: ``reset_launch_count(); jax.jit(step).lower(...); launch_count()``
#: counts the compiled step's kernel launches (the benchmark's
#: one-launch-per-step guard). Interpret/compiled mode agnostic.
_LAUNCHES = {"count": 0}


def reset_launch_count() -> None:
    _LAUNCHES["count"] = 0


def launch_count() -> int:
    return _LAUNCHES["count"]


def _count_launch() -> None:
    _LAUNCHES["count"] += 1


def _pad2(x: jax.Array, rows: int, cols: int) -> jax.Array:
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, cols - x.shape[1])))


def row_table(x: jax.Array) -> jax.Array:
    """(..., rows, W) -> (..., rows, 1, W_p) float32, zero-padded to a
    LANE multiple: the row layout the fused kernel DMAs single rows from
    (see the module docstring). Lay a table out once and keep it."""
    w = x.shape[-1]
    pad = [(0, 0)] * (x.ndim - 1) + [(0, round_up(max(w, 1)) - w)]
    return jnp.pad(x.astype(jnp.float32), pad)[..., None, :]


# ---------------------------------------------------------------------------
# GRU memory update
# ---------------------------------------------------------------------------


def pad_gru_params(params: dict, f_mail: int, f_mem: int) -> dict:
    """Repack core-layout GRU params into lane-aligned kernel layout.

    core layout: w_i (f_mail, 3*f_mem) with gates at f_mem strides.
    kernel layout: (f_mail_p, 3*m_p) with gates at m_p strides.
    Precompute once per model; reuse across calls.
    """
    f_p, m_p = round_up(f_mail), round_up(f_mem)

    def repack_w(w, in_dim, in_p):
        gates = [w[:, g * f_mem:(g + 1) * f_mem] for g in range(3)]
        return jnp.concatenate(
            [_pad2(g, in_p, m_p) for g in gates], axis=1)

    def repack_b(b):
        gates = [b[g * f_mem:(g + 1) * f_mem] for g in range(3)]
        return jnp.concatenate(
            [jnp.pad(g, (0, m_p - f_mem)) for g in gates])[None, :]

    return {
        "w_i": repack_w(params["w_i"], f_mail, f_p),
        "w_h": repack_w(params["w_h"], f_mem, m_p),
        "b_i": repack_b(params["b_i"]),
        "b_h": repack_b(params["b_h"]),
    }


def repack_gate_rows(x: jax.Array, f_mem: int, m_p: int) -> jax.Array:
    """Per-row gate vectors (B, 3*f_mem) [r|z|n at f_mem strides] ->
    lane-aligned (B, 3*m_p)."""
    gates = [x[:, g * f_mem:(g + 1) * f_mem] for g in range(3)]
    return jnp.concatenate(
        [jnp.pad(g, ((0, 0), (0, m_p - f_mem))) for g in gates], axis=1)


def gru_cell(mail: jax.Array, s: jax.Array, packed: dict,
             extra: jax.Array | None = None, *,
             block_b: int = 128) -> jax.Array:
    """Fused GRU cell on native dims. mail (B, f_mail), s (B, f_mem);
    ``packed`` from pad_gru_params; ``extra`` optional (B, 3*f_mem) additive
    input-gate rows in core layout (LUT-folded time rows, §III-C).
    Returns (B, f_mem)."""
    B, f_mail = mail.shape
    f_mem = s.shape[-1]
    f_p = packed["w_i"].shape[0]
    m_p = packed["w_h"].shape[0]
    _count_launch()
    bb = min(block_b, round_up(B, 8))
    B_p = round_up(B, bb)
    mail_p = _pad2(mail.astype(jnp.float32), B_p, f_p)
    s_p = _pad2(s.astype(jnp.float32), B_p, m_p)
    if extra is None:
        extra_p = jnp.zeros((B_p, 3 * m_p), jnp.float32)
    else:
        extra_p = _pad2(repack_gate_rows(extra.astype(jnp.float32),
                                         f_mem, m_p), B_p, 3 * m_p)
    out = gru_cell_pallas(mail_p, s_p, extra_p, packed["w_i"], packed["w_h"],
                          packed["b_i"], packed["b_h"], block_b=bb,
                          interpret=_use_interpret())
    return out[:B, :f_mem]


# ---------------------------------------------------------------------------
# LUT time encode
# ---------------------------------------------------------------------------


def _sentinel_bounds(boundaries: jax.Array, E: int) -> jax.Array:
    """bounds (E-1,) -> (1, E) with the +inf sentinel — the ONE definition
    of the kernel-side boundary layout (pad_lut_params, pad_sat_params and
    pad_fused_params all feed the same in-kernel bucketing,
    lut_time_encode.lut_rows; a drift here would desynchronize tiers)."""
    return jnp.concatenate(
        [boundaries.astype(jnp.float32),
         jnp.full((E - boundaries.shape[0],), np.inf,
                  jnp.float32)])[None, :]


def pad_lut_params(boundaries: jax.Array, table: jax.Array) -> dict:
    """bounds (E-1,) -> (1, E) with +inf sentinel; table (E, D) -> (E, D_p)."""
    E, D = table.shape
    return {"bounds": _sentinel_bounds(boundaries, E),
            "table": _pad2(table.astype(jnp.float32), E, round_up(D)),
            "d": D}


def lut_encode(dt: jax.Array, packed: dict) -> jax.Array:
    """dt (...,) -> (..., D) via the LUT kernel."""
    _count_launch()
    shape = dt.shape
    flat = dt.reshape(-1).astype(jnp.float32)
    B = flat.shape[0]
    bb = min(256, round_up(B, 8))
    B_p = round_up(B, bb)
    flat = jnp.pad(flat, (0, B_p - B))
    out = lut_encode_pallas(flat, packed["bounds"], packed["table"],
                            block_b=bb, interpret=_use_interpret())
    return out[:B, :packed["d"]].reshape(*shape, packed["d"])


# ---------------------------------------------------------------------------
# SAT aggregation
# ---------------------------------------------------------------------------


def pad_sat_params(w_v: jax.Array, b_v: jax.Array, boundaries: jax.Array,
                   folded_table: jax.Array) -> dict:
    """w_v (Dkv, D) [memory||edge rows only], b_v (D,), folded LUT table
    (E, D) already = table @ W_v[time rows]."""
    dkv, d = w_v.shape
    dkv_p, d_p = round_up(dkv), round_up(d)
    E = folded_table.shape[0]
    return {
        "w_v": _pad2(w_v.astype(jnp.float32), dkv_p, d_p),
        "b_v": jnp.pad(b_v.astype(jnp.float32), (0, d_p - d))[None, :],
        "bounds": _sentinel_bounds(boundaries, E),
        "table": _pad2(folded_table.astype(jnp.float32), E, d_p),
        "dkv": dkv, "d": d,
    }


def sat_aggregate(kv: jax.Array, dt: jax.Array, logits: jax.Array,
                  valid: jax.Array, packed: dict,
                  *, block_b: int = 128) -> jax.Array:
    """Fused student EU tail. kv (B, k, dkv); dt/logits (B, k);
    valid (B, k) bool. Returns (B, d)."""
    _count_launch()
    B, k, dkv = kv.shape
    dkv_p = packed["w_v"].shape[0]
    bb = min(block_b, round_up(B, 8))
    B_p = round_up(B, bb)
    kv_p = jnp.pad(kv.astype(jnp.float32),
                   ((0, B_p - B), (0, 0), (0, dkv_p - dkv)))
    pad_rows = ((0, B_p - B), (0, 0))
    dt_flat = jnp.pad(dt.astype(jnp.float32), pad_rows).reshape(B_p * k, 1)
    out = sat_aggregate_pallas(
        kv_p, dt_flat, jnp.pad(logits.astype(jnp.float32), pad_rows),
        jnp.pad(valid.astype(jnp.float32), pad_rows),
        packed["w_v"], packed["b_v"], packed["bounds"], packed["table"],
        block_b=bb, interpret=_use_interpret())
    return out[:B, :packed["d"]]


# ---------------------------------------------------------------------------
# Fused single-pass step (scalar-prefetch gather + one-launch MUU/EU)
# ---------------------------------------------------------------------------


def pad_fused_params(gru_params: dict, attn_params: dict, folded_gru: dict,
                     folded_attn: dict, f_mail_raw: int, f_mem: int,
                     f_edge: int) -> dict:
    """Kernel-layout parameter pack for the fused single-pass step.

    Everything the one-launch datapath consumes, lane-padded (the row
    tables the kernel DMAs from carry zero lane padding, so zero-padding
    weight ROWS keeps the math exact):

      * the raw-mail GRU weights at m_p gate strides (pad_gru_params) plus
        the GRU-folded LUT table gate-repacked to (E, 3*m_p);
      * W_v split at the memory/edge boundary — the kernel computes the kv
        projection as TWO matmuls, so the ``(B, k, Dkv)`` concat never
        exists — plus the attention-folded LUT table (E, d_p);
      * the output transform split the same way (self rows || aggregate).
    """
    m_p = round_up(f_mem)
    e_p = round_up(max(f_edge, 1))
    d = attn_params["w_v"].shape[1]
    d_p = round_up(d)
    f_emb = attn_params["w_out"].shape[1]
    emb_p = round_up(f_emb)
    E = folded_gru["table"].shape[0]

    gru = pad_gru_params(
        {"w_i": gru_params["w_i"][:f_mail_raw], "w_h": gru_params["w_h"],
         "b_i": gru_params["b_i"], "b_h": gru_params["b_h"]},
        f_mail_raw, f_mem)
    w_v = attn_params["w_v"]
    wv_edge = (w_v[f_mem:f_mem + f_edge] if f_edge
               else jnp.zeros((1, d), jnp.float32))
    w_out = attn_params["w_out"]
    return {
        "w_i": gru["w_i"], "w_h": gru["w_h"],
        "b_i": gru["b_i"], "b_h": gru["b_h"],
        "g_bounds": _sentinel_bounds(folded_gru["boundaries"], E),
        "g_table": _pad2(repack_gate_rows(
            folded_gru["table"].astype(jnp.float32), f_mem, m_p), E,
            3 * m_p),
        "wv_mem": _pad2(w_v[:f_mem].astype(jnp.float32), m_p, d_p),
        "wv_edge": _pad2(wv_edge.astype(jnp.float32), e_p, d_p),
        "b_v": jnp.pad(attn_params["b_v"].astype(jnp.float32),
                       (0, d_p - d))[None, :],
        "s_bounds": _sentinel_bounds(folded_attn["boundaries"], E),
        "s_table": _pad2(folded_attn["table"].astype(jnp.float32), E, d_p),
        "w_self": _pad2(w_out[:f_mem].astype(jnp.float32), m_p, emb_p),
        "w_agg": _pad2(w_out[f_mem:].astype(jnp.float32), d_p, emb_p),
        "b_out": jnp.pad(attn_params["b_out"].astype(jnp.float32),
                         (0, emb_p - f_emb))[None, :],
        "f_mem": f_mem, "f_edge": f_edge, "f_mail": f_mail_raw,
        "f_emb": f_emb,
    }


def fused_step(vids: jax.Array, sel_ids: jax.Array, sel_eid: jax.Array,
               hit: jax.Array, dt_mail: jax.Array, mail_ok: jax.Array,
               sel_dt: jax.Array, sel_logits: jax.Array,
               sel_valid: jax.Array, memory: jax.Array, mail: jax.Array,
               edge_feats: jax.Array, packed: dict,
               *, block_b: int = 128):
    """ONE launch for the post-prune datapath: winner-row gather + kv
    projection + folded-LUT rows + masked softmax + FAM + output transform
    + GRU memory update.

    ``vids`` (R,) int; ``sel_ids``/``sel_eid``/``hit`` (R, k) int —
    ``hit[r, j] >= 0`` marks a winner whose vertex is updated by THIS
    batch and names the batch row holding its updated memory (the
    committed view); ``dt_mail``/``mail_ok`` (R,); ``sel_dt``/
    ``sel_logits``/``sel_valid`` (R, k). ``memory``/``mail``/
    ``edge_feats`` are the HBM-resident tables in the ``row_table``
    layout — the kernel fetches only the addressed rows, and nothing here
    copies a table. Returns ``(h (R, f_emb), s_upd (R, f_mem))``.
    """
    _count_launch()
    R, k = sel_ids.shape
    bb = min(block_b, round_up(R, 8))
    R_p = round_up(R, bb)
    pad = R_p - R
    p1, p2 = ((0, pad),), ((0, pad), (0, 0))

    def i32(x, padder=p1, fill=0):
        return jnp.pad(x.astype(jnp.int32), padder, constant_values=fill)

    def f32(x, padder=p1):
        return jnp.pad(x.astype(jnp.float32), padder)

    h, s_upd = fused_step_pallas(
        i32(vids), i32(sel_ids, p2).reshape(-1),
        i32(sel_eid, p2).reshape(-1),
        i32(hit, p2, fill=-1).reshape(-1),
        f32(dt_mail)[:, None], f32(mail_ok)[:, None],
        f32(sel_dt, p2).reshape(R_p * k, 1), f32(sel_logits, p2),
        f32(sel_valid, p2), memory, mail, edge_feats,
        packed["w_i"], packed["w_h"], packed["b_i"], packed["b_h"],
        packed["g_bounds"], packed["g_table"], packed["wv_mem"],
        packed["wv_edge"], packed["b_v"], packed["s_bounds"],
        packed["s_table"], packed["w_self"], packed["w_agg"],
        packed["b_out"],
        k=k, f_edge=packed["f_edge"], block_b=bb,
        interpret=_use_interpret())
    return h[:R, :packed["f_emb"]], s_upd[:R, :packed["f_mem"]]
