"""Fused SAT aggregation Pallas kernel — the Embedding Unit (§IV-B) on TPU.

Covers the FLOP-heavy tail of the student model's embedding step, AFTER the
prune-then-fetch gather (top-k selection over (B, m_r) logits is metadata
work left to XLA; the gather itself is the HBM saving the paper is after and
happens before this kernel — only k rows per vertex ever reach it):

  v      = kv_sel @ W_v  +  LUT_folded[bucket(dt_sel)]  +  b_v     (Eq. 14,
           with the time-encoding rows pre-folded through W_v, §III-C)
  attn   = masked_softmax(sel_logits)                              (Eq. 16)
  h_agg  = sum_k attn_k * v_k                                      (FAM)

The LUT row fetch is realised as one_hot(bucket) @ table so it runs on the
MXU (TPU has no cheap scalar gather from VMEM; a (Bk,128)x(128,D) matmul is
fully pipelined) — see DESIGN.md §2.

Per grid step the working set is one batch tile of neighbors
(block_b * k, Dkv) plus the weights (Dkv, D) and the folded table (128, D) —
for paper dims (k<=10, Dkv=384, D=128) well under 2 MiB of VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.utils import NEG_INF  # single source of truth (see utils.py)
from repro.kernels.lut_time_encode import lut_rows


def _sat_kernel(kv_ref, dt_ref, logits_ref, valid_ref, w_v_ref, b_v_ref,
                bounds_ref, table_ref, out_ref, *, k: int, n_entries: int):
    """One batch tile.  Shapes (VMEM):
    kv (Bb, k*Dkv) — k pre-gathered neighbor rows, flattened;
    dt (Bb*k, 1) — flattened by the caller, so no lane->sublane reshape
    happens in the kernel; logits (Bb, k), valid (Bb, k) float {0,1};
    w_v (Dkv, D), b_v (1, D), bounds (1, n_entries), table (n_entries, D);
    out (Bb, D).
    """
    bb = kv_ref.shape[0]
    dkv = kv_ref.shape[1] // k
    d = w_v_ref.shape[1]

    kv = kv_ref[...].reshape(bb * k, dkv)
    v = jnp.dot(kv, w_v_ref[...], preferred_element_type=jnp.float32)

    # LUT time rows (lut_time_encode.lut_rows: the one shared bucketing
    # definition across every kernel tier)
    v = v + lut_rows(dt_ref[...], bounds_ref, table_ref, n_entries)
    v = v + b_v_ref[...]
    v = v.reshape(bb, k, d)

    # masked softmax over the k surviving neighbors
    valid = valid_ref[...]
    logits = jnp.where(valid > 0, logits_ref[...], NEG_INF)
    mx = jnp.max(logits, axis=1, keepdims=True)
    e = jnp.exp(logits - mx) * valid
    z = jnp.sum(e, axis=1, keepdims=True)
    attn = jnp.where(z > 0, e / jnp.maximum(z, 1e-30), 0.0)  # (Bb, k)

    out_ref[...] = jnp.sum(attn[:, :, None] * v, axis=1)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def sat_aggregate_pallas(kv: jax.Array, dt: jax.Array, logits: jax.Array,
                         valid: jax.Array, w_v: jax.Array, b_v: jax.Array,
                         bounds: jax.Array, table: jax.Array,
                         *, block_b: int = 128,
                         interpret: bool = False) -> jax.Array:
    """Fused V-projection + LUT + masked-softmax aggregation.

    kv (B, k, Dkv) float32 — pruned, pre-gathered neighbor features (memory
    || edge feature), zero where invalid; dt (B*k, 1), row-major over
    (B, k); logits (B, k); valid (B, k) float {0,1}; w_v (Dkv, D);
    b_v (1, D); bounds (1, E); table (E, D).
    B multiple of block_b; Dkv and D LANE-aligned. Returns (B, D).
    """
    B, k, dkv = kv.shape
    d = w_v.shape[1]
    E = table.shape[0]
    assert B % block_b == 0, (B, block_b)
    assert bounds.shape == (1, E)
    assert dt.shape == (B * k, 1), (dt.shape, B, k)
    grid = (B // block_b,)
    return pl.pallas_call(
        functools.partial(_sat_kernel, k=k, n_entries=E),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, k * dkv), lambda i: (i, 0)),
            pl.BlockSpec((block_b * k, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, k), lambda i: (i, 0)),
            pl.BlockSpec((block_b, k), lambda i: (i, 0)),
            pl.BlockSpec((dkv, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),
            pl.BlockSpec((E, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, d), jnp.float32),
        interpret=interpret,
    )(kv.reshape(B, k * dkv), dt, logits, valid, w_v, b_v, bounds, table)
