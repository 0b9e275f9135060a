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


def _student(spec="sat+lut+np4", tier="fused", **dims):
    pipe = pl.build_pipeline(spec, use_kernels=tier, **{**DIMS, **dims})
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


#: the fused step's shapes: Wikipedia's, and GDELT's (paper Table II:
#: 200-d static node features, no edge features, so the session's
#: one-row edge placeholder; 16,682 actors, TGL arXiv:2203.14883)
FUSED_SHAPES = {"wikipedia": {},
                "gdelt": dict(n_nodes=16_682, n_edges=1, f_edge=0,
                              f_feat=200)}


@pytest.mark.parametrize("shape", sorted(FUSED_SHAPES))
def test_fused_step_compiles(one_chip, shape):
    pipe, params, aux = _student(**FUSED_SHAPES[shape])
    cfg = pipe.cfg
    state = jax.eval_shape(lambda: pipe.resident(pipe.init_state()))
    edge_tab = jax.eval_shape(ops.row_table, jax.ShapeDtypeStruct(
        (cfg.n_edges, cfg.f_edge), jnp.float32))
    node_tab = (jax.eval_shape(ops.row_table, jax.ShapeDtypeStruct(
        (cfg.n_nodes, cfg.f_feat), jnp.float32)) if cfg.f_feat else None)
    zi = jnp.zeros((B,), jnp.int32)
    batch = (zi, zi, zi, jnp.zeros((B,), jnp.float32),
             jnp.zeros((B,), bool))
    text = _compile(lambda st, b, ef, nf: pipe.step(params, aux, st, b, ef,
                                                    nf),
                    state, batch, edge_tab, node_tab, sharding=one_chip)
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


def _entry_ops(text):
    """``{instruction: (result type, opcode, op name)}`` of the compiled
    program's entry computation: the operations a device trace shows as
    ``XLA Ops`` events (the insides of fusions are not events of their
    own)."""
    import re
    ops, inside = {}, False
    for line in text.splitlines():
        if line.startswith("ENTRY "):
            inside = True
            continue
        if inside and line.startswith("}"):
            break
        m = inside and re.match(
            r"^\s*(?:ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\(", line)
        if m:
            label = re.search(r'metadata=\{op_name="([^"]*)"', line)
            ops[m.group(1)] = (m.group(2), m.group(3),
                               label.group(1) if label else "")
    return ops


def test_commit_ops_carry_the_lww_commit_scope(one_chip):
    """The last-write-wins commits of a GDELT-shaped fused round (memory,
    last update and mail flags; mail and its time stamp) compile to
    entry-level operations whose op names carry ``lww_commit``: each
    scatter into a table with the committer's scratch row (V + 1 rows),
    the pad that appends that row and the slice that drops it. The copies
    XLA inserts on its own (``copy``, ``copy-start``/``copy-done``) carry
    no op name. The benchmark's ``round.lww_commit_ms`` sums the device
    time of exactly the events labelled ``lww_commit``."""
    import importlib.util
    import sys
    bench = os.path.join(os.path.dirname(__file__), os.pardir, "bench")
    sys.path.insert(0, bench)
    try:
        import trace_reduce
    finally:
        sys.path.remove(bench)
    n, v = 2, 2000
    pipe, params, aux = _student(**{**FUSED_SHAPES["gdelt"], "n_nodes": v})
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n,) + x.shape, x.dtype),
        jax.eval_shape(lambda: pipe.resident(pipe.init_state())))
    sb = (np.zeros((n, B), np.int32), np.zeros((n, B), np.int32),
          np.zeros((n, B), np.int32), np.zeros((n, B), np.float32),
          np.zeros((n, B), bool))
    edge_tab = jax.ShapeDtypeStruct((1, 1, 128), jnp.float32)
    node_tab = jax.ShapeDtypeStruct((v, 1, 256), jnp.float32)
    rnd = pl.CoalescedRound([(pipe, aux, n)])
    with ops.force_interpret(False):
        lowered = rnd._fn.lower(*_shapes(((params,), (state,), sb, edge_tab,
                                          node_tab), one_chip), (B,))
    text = lowered.compile().as_text()
    entry = _entry_ops(text)
    lww = {k for k, (_t, _o, label) in entry.items() if "lww_commit" in label}
    # every f32/bool table of V + 1 rows that an operation computes is the
    # committer's (the fused datapath's int32 winner-row redirect is the
    # one other such table); bitcasts and XLA's copies compute nothing
    moved = ("bitcast", "copy", "copy-start", "copy-done")
    scratch = {k for k, (t, op, _l) in entry.items() if op not in moved
               and t.startswith((f"f32[{n},{v + 1}", f"pred[{n},{v + 1}"))}
    assert scratch and scratch <= lww, sorted(scratch - lww)
    assert not any(entry[k][2] for k, (_t, op, _l) in entry.items()
                   if op.startswith("copy-")), "XLA's copies now carry a name"
    kinds = [entry[k][2].rsplit("/", 1)[-1] for k in lww]
    assert kinds.count("scatter") >= 6, kinds        # six committed tables
    assert "concatenate" in kinds and "slice" in kinds, kinds
    # the reader sums the labelled events, and only those
    spec = importlib.util.spec_from_file_location(
        "lww_reader", os.path.join(bench, "metrics", "round.lww_commit_ms.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    labels = trace_reduce.op_labels(text)
    rec = {"window_rounds": 4, "trace": {"ops": {
        (f"{k} {labels[k]}" if k in labels else k): 1e-3 for k in entry}}}
    assert reader.read(rec) == pytest.approx(len(lww) * 1e-3 / 4 * 1e3)
