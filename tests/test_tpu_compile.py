"""Compile-only rehearsals for a described TPU v5e (no chip attached).

Interpret mode (every other kernel test) cannot show that a Pallas kernel
compiles: the chip's compiler refuses unaligned row slices, lane->sublane
reshapes and oversized VMEM working sets that the interpreter accepts. Each
test here lowers one kernel, or the coalesced serving round, at the paper's
widths (f_mem = f_time = f_emb = 100, f_edge = 172, m_r = 10, batch 200) on
the Wikipedia-shaped tables (9,227 vertices, 157,474 edges), compiles it
for one device of a described ``v5e:2x2`` topology, and checks that the
compiled program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pipeline as pl
from repro.kernels import ops

B = 200                       # edges per tenant batch
R = 2 * B                     # involved vertex instances per batch
V, E = 9227, 157_474          # Wikipedia-shaped tables
DIMS = dict(n_nodes=V, n_edges=E, f_edge=172, f_mem=100, f_time=100,
            f_emb=100, m_r=10)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU library to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to a persistent cache
    # but cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    """ShapeDtypeStructs of ``tree`` placed on the described device."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile(fn, *args, sharding):
    """Lower ``fn`` with compiled (not interpreted) kernels and compile it
    for the described chip; returns the optimized HLO text."""
    with ops.force_interpret(False):
        lowered = jax.jit(fn).lower(*_shapes(args, sharding))
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _student(spec="sat+lut+np4", tier="fused"):
    pipe = pl.build_pipeline(spec, use_kernels=tier, **DIMS)
    params = pipe.init_params(jax.random.key(0))
    return pipe, params, pipe.prepare(params)


def test_lut_encode_compiles(one_chip):
    _, _, aux = _student(tier="staged")
    packed = aux["packed_lut_gru"]
    _compile(lambda dt: ops.lut_encode(dt, packed),
             jnp.zeros((R,), jnp.float32), sharding=one_chip)


def test_gru_cell_compiles(one_chip):
    pipe, _, aux = _student(tier="staged")
    f_mail = pipe.cfg.gru.f_mail_raw
    _compile(lambda mail, s, x: ops.gru_cell(mail, s, aux["packed_gru"],
                                             extra=x),
             jnp.zeros((R, f_mail)), jnp.zeros((R, 100)),
             jnp.zeros((R, 300)), sharding=one_chip)


def test_sat_aggregate_compiles(one_chip):
    _, _, aux = _student(tier="staged")
    k = 4
    _compile(lambda kv, dt, lg, ok: ops.sat_aggregate(kv, dt, lg, ok,
                                                      aux["packed_sat"]),
             jnp.zeros((R, k, 272)), jnp.zeros((R, k)), jnp.zeros((R, k)),
             jnp.zeros((R, k), bool), sharding=one_chip)


def test_fused_step_compiles(one_chip):
    pipe, params, aux = _student()
    state = jax.eval_shape(lambda: pipe.resident(pipe.init_state()))
    edge_tab = jax.eval_shape(ops.row_table,
                              jax.ShapeDtypeStruct((E, 172), jnp.float32))
    zi = jnp.zeros((B,), jnp.int32)
    batch = (zi, zi, zi, jnp.zeros((B,), jnp.float32),
             jnp.zeros((B,), bool))
    text = _compile(lambda st, b, ef: pipe.step(params, aux, st, b, ef),
                    state, batch, edge_tab, sharding=one_chip)
    assert text.count("tpu_custom_call") >= 1


def test_mixed_cohort_round_compiles(one_chip):
    """The coalesced round of a 3-lane fleet — fused np4, staged np2 and
    fused reservoir, one tenant each — compiles as one program."""
    lanes = (("sat+lut+np4", "fused"), ("sat+lut+np2", "staged"),
             ("sat+lut+np4+reservoir", "fused"))
    parts, params, states = [], [], []
    for spec, tier in lanes:
        pipe, p, aux = _student(spec, tier)
        parts.append((pipe, aux, 1))
        params.append(p)
        states.append(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype),
            jax.eval_shape(lambda: pipe.resident(pipe.init_state()))))
    rnd = pl.CoalescedRound(parts)
    n = len(lanes)
    sb = (np.zeros((n, B), np.int32), np.zeros((n, B), np.int32),
          np.zeros((n, B), np.int32), np.zeros((n, B), np.float32),
          np.zeros((n, B), bool))
    edge_tab = jax.ShapeDtypeStruct((E, 1, 256), jnp.float32)
    with ops.force_interpret(False):
        lowered = rnd._fn.lower(*_shapes((tuple(params), tuple(states), sb,
                                          edge_tab), one_chip),
                                None, (B,) * n)
    text = lowered.compile().as_text()
    # one Mosaic launch per fused lane, three per staged lane
    assert text.count("tpu_custom_call") >= 2 + 3
