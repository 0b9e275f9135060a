"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode).

Also the fused single-pass step kernel (kernels/fused_step.py) against the
staged-kernel composition — the equivalence contract ``use_kernels="fused"``
must keep for every registered variant (make test-kernels runs this file).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.utils
from repro.core import memory as mem_mod, pruning, time_encode as te
from repro.kernels import ops, ref
from repro.kernels import sat_aggregate as sat_mod


@pytest.mark.parametrize("B", [1, 5, 128, 200])
@pytest.mark.parametrize("f_mem,f_edge", [(100, 172), (32, 16), (128, 0)])
def test_gru_kernel_matches_core(B, f_mem, f_edge):
    cfg = mem_mod.GRUConfig(f_mem=f_mem, f_edge=f_edge, f_time=f_mem)
    params = mem_mod.init_gru(jax.random.key(0), cfg)
    rng = np.random.RandomState(B + f_mem)
    mail = jnp.asarray(rng.randn(B, cfg.f_mail), jnp.float32)
    s = jnp.asarray(rng.randn(B, f_mem), jnp.float32)
    want = mem_mod.gru_cell(params, mail, s)
    packed = ops.pad_gru_params(params, cfg.f_mail, f_mem)
    got = ops.gru_cell(mail, s, packed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_gru_kernel_extra_rows_equal_lut_path():
    cfg = mem_mod.GRUConfig(f_mem=48, f_edge=24, f_time=16)
    params = mem_mod.init_gru(jax.random.key(1), cfg)
    rng = np.random.RandomState(7)
    B = 33
    mail_raw = jnp.asarray(rng.randn(B, cfg.f_mail_raw), jnp.float32)
    time_rows = jnp.asarray(rng.randn(B, 3 * cfg.f_mem), jnp.float32)
    s = jnp.asarray(rng.randn(B, cfg.f_mem), jnp.float32)
    want = mem_mod.gru_cell_lut(params, mail_raw, time_rows, s)
    packed = ops.pad_gru_params(
        {"w_i": params["w_i"][:cfg.f_mail_raw], "w_h": params["w_h"],
         "b_i": params["b_i"], "b_h": params["b_h"]},
        cfg.f_mail_raw, cfg.f_mem)
    got = ops.gru_cell(mail_raw, s, packed, extra=time_rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B", [1, 7, 300])
@pytest.mark.parametrize("dim", [100, 64])
def test_lut_kernel_matches_core(B, dim):
    tcfg = te.TimeEncoderConfig(dim=dim, n_entries=128)
    lut = te.init_lut(jax.random.key(2), tcfg)
    rng = np.random.RandomState(B)
    dt = jnp.asarray(10 ** rng.uniform(0, 7, (B,)), jnp.float32)
    want = te.lut_encode(lut, dt)
    packed = ops.pad_lut_params(lut["boundaries"], lut["table"])
    got = ops.lut_encode(dt, packed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_lut_kernel_boundary_values_exact():
    """dt exactly at a boundary must land in the upper bucket (>= compare),
    in both kernel and core paths."""
    tcfg = te.TimeEncoderConfig(dim=8, n_entries=16)
    lut = te.init_lut(jax.random.key(3), tcfg,
                      dt_samples=np.linspace(1, 1000, 500))
    bounds = np.asarray(lut["boundaries"])
    dt = jnp.asarray(np.concatenate([bounds, bounds - 1e-3, [0.0, 1e9]]),
                     jnp.float32)
    want = te.lut_encode(lut, dt)
    packed = ops.pad_lut_params(lut["boundaries"], lut["table"])
    got = ops.lut_encode(dt, packed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("B,k", [(1, 2), (37, 4), (128, 10)])
@pytest.mark.parametrize("dkv,d", [(272, 100), (48, 32)])
def test_sat_kernel_matches_ref(B, k, dkv, d):
    rng = np.random.RandomState(B * k)
    E = 128
    kv = jnp.asarray(rng.randn(B, k, dkv), jnp.float32)
    dt = jnp.asarray(10 ** rng.uniform(0, 6, (B, k)), jnp.float32)
    logits = jnp.asarray(rng.randn(B, k), jnp.float32)
    valid = jnp.asarray(rng.rand(B, k) > 0.3)
    w_v = jnp.asarray(rng.randn(dkv, d) * 0.05, jnp.float32)
    b_v = jnp.asarray(rng.randn(d) * 0.1, jnp.float32)
    folded = jnp.asarray(rng.randn(E, d) * 0.05, jnp.float32)
    bounds = jnp.sort(jnp.asarray(10 ** rng.uniform(0, 6, (E - 1,)),
                                  jnp.float32))
    packed = ops.pad_sat_params(w_v, b_v, bounds, folded)
    got = ops.sat_aggregate(kv, dt, logits, valid, packed)
    want = ref.sat_aggregate_ref(kv, dt, logits, valid.astype(jnp.float32),
                                 w_v, b_v, bounds[None, :], folded)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_sat_kernel_all_invalid_row_is_zero():
    rng = np.random.RandomState(0)
    B, k, dkv, d, E = 4, 3, 48, 32, 128
    kv = jnp.asarray(rng.randn(B, k, dkv), jnp.float32)
    dt = jnp.ones((B, k), jnp.float32)
    logits = jnp.zeros((B, k), jnp.float32)
    valid = jnp.zeros((B, k), bool)
    packed = ops.pad_sat_params(
        jnp.asarray(rng.randn(dkv, d), jnp.float32),
        jnp.zeros((d,), jnp.float32),
        jnp.sort(jnp.asarray(rng.rand(E - 1) * 100, jnp.float32)),
        jnp.asarray(rng.randn(E, d), jnp.float32))
    got = ops.sat_aggregate(kv, dt, logits, valid, packed)
    np.testing.assert_allclose(np.asarray(got), 0.0)


def test_neg_inf_is_single_sourced():
    """The logit mask value must have exactly one definition (utils) —
    a kernel/ref drift would silently break fused-vs-staged equivalence."""
    assert pruning.NEG_INF is repro.utils.NEG_INF
    assert ref.NEG_INF is repro.utils.NEG_INF
    assert sat_mod.NEG_INF is repro.utils.NEG_INF
    from repro.kernels import fused_step as fused_mod
    assert fused_mod.NEG_INF is repro.utils.NEG_INF


# ---------------------------------------------------------------------------
# fused single-pass step vs the staged-kernel composition
# ---------------------------------------------------------------------------

#: every registered prune budget and sampler backend the student ladder
#: serves (the score-all sat+lut row exercises k == m_r).
FUSED_VARIANTS = ("sat+lut", "sat+lut+np6", "sat+lut+np4", "sat+lut+np2",
                  "sat+lut+np4+uniform", "sat+lut+np4+reservoir")


def _fused_setup(variant, key=0, f=16, n_edges=300):
    from repro.core import pipeline as pl
    from repro.data import temporal_graph as tgd
    g = tgd.wikipedia_like(n_edges=n_edges)
    dims = dict(n_nodes=g.cfg.n_nodes, n_edges=g.n_edges, f_edge=172,
                f_mem=f, f_time=f, f_emb=f, m_r=10)
    cfg = pl.variant_config(variant, **dims)
    staged = pl.build_pipeline(cfg, use_kernels=True)
    fused = pl.build_pipeline(cfg, use_kernels="fused")
    params = staged.init_params(jax.random.key(key))
    return g, staged, fused, params


def _batches(g, n, B, ragged=False):
    from repro.data import stream as stream_mod
    out = []
    for i, b in enumerate(stream_mod.fixed_count(
            g, B, window=slice(0, n * B))):
        valid = np.asarray(b.valid).copy()
        if ragged and i == 1:
            valid[B // 2:] = False        # ragged round: half padding
        out.append(tuple(jnp.asarray(x) for x in
                         (b.src, b.dst, b.eid, b.ts, valid)))
    return out


@pytest.mark.parametrize("variant", FUSED_VARIANTS)
def test_fused_step_matches_staged_trajectory(variant):
    """The one-launch fused step reproduces the staged-kernel trajectory
    (state AND embeddings AND distill views) within the staged kernels'
    own tolerances, for every prune budget / sampler backend — including
    a ragged round whose padding rows must commit nothing."""
    g, staged, fused, params = _fused_setup(variant)
    ef = jnp.asarray(g.edge_feats)
    ef_rows = ops.row_table(ef)
    ss, sf = staged.init_state(), fused.resident(fused.init_state())
    assert fused.tier == "fused" and fused.stages.fused is not None
    for b in _batches(g, 4, 30, ragged=True):
        os_ = staged.step_fn(params, ss, b, ef)
        of_ = fused.step_fn(params, sf, b, ef_rows)
        ss, sf = os_.state, of_.state
        sf_native = fused.native(sf)
        m = np.asarray(b[4])[:, None]
        np.testing.assert_allclose(
            np.asarray((os_.emb_src - of_.emb_src)) * m, 0.0, atol=2e-5)
        np.testing.assert_allclose(
            np.asarray((os_.emb_dst - of_.emb_dst)) * m, 0.0, atol=2e-5)
        np.testing.assert_array_equal(np.asarray(os_.nbr_valid),
                                      np.asarray(of_.nbr_valid))
        np.testing.assert_allclose(np.asarray(os_.attn_logits),
                                   np.asarray(of_.attn_logits), atol=1e-5)
        for field in ("memory", "mail", "last_update", "mail_ts",
                      "mail_valid", "nbr_ids", "nbr_ts", "nbr_eid",
                      "nbr_cursor"):
            np.testing.assert_allclose(
                np.asarray(getattr(ss, field)),
                np.asarray(getattr(sf_native, field)), atol=2e-5,
                err_msg=f"{variant}:{field}")


def test_fused_step_all_invalid_batch_is_bitwise_noop():
    """A fully-masked batch (idle tenant) through the fused launch leaves
    the vertex state bitwise untouched — the idle-masking contract every
    serving layer relies on."""
    g, staged, fused, params = _fused_setup("sat+lut+np4", key=3)
    ef = ops.row_table(jnp.asarray(g.edge_feats))
    state = fused.resident(fused.init_state())
    for b in _batches(g, 2, 25):
        state = fused.step_fn(params, state, b, ef).state
    B = 13
    zi = jnp.zeros((B,), jnp.int32)
    bad = (zi, zi, zi, jnp.zeros((B,), jnp.float32), jnp.zeros((B,), bool))
    out = fused.step_fn(params, state, bad, ef)
    for f in state._fields:
        np.testing.assert_array_equal(np.asarray(getattr(state, f)),
                                      np.asarray(getattr(out.state, f)),
                                      err_msg=f)


def test_fused_step_is_one_kernel_launch():
    """The post-prune datapath is exactly ONE pallas launch per step under
    the fused tier; the staged tier pays one per unit (LUT + GRU + SAT)."""
    g, staged, fused, params = _fused_setup("sat+lut+np4", key=1)
    ef = jnp.asarray(g.edge_feats)
    b = _batches(g, 1, 20)[0]
    aux_s, aux_f = staged.prepare(params), fused.prepare(params)

    ops.reset_launch_count()
    jax.jit(lambda s: staged.step(params, aux_s, s, b, ef)).lower(
        staged.init_state())
    assert ops.launch_count() == 3
    ops.reset_launch_count()
    ef_rows = ops.row_table(ef)
    jax.jit(lambda s: fused.step(params, aux_f, s, b, ef_rows)).lower(
        fused.resident(fused.init_state()))
    assert ops.launch_count() == 1
