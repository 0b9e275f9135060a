"""The plain reference: TGN's per-batch step in straightforward jax.numpy,
and the benchmark's own weights.

It imports nothing of the program. It follows TGN (Rossi et al. 2020,
arXiv:2006.10637) as the co-design paper (arXiv:2203.05095) serves it,
one chronological batch of edges at a time:

1. every vertex instance of the batch (sources, then destinations)
   consumes its cached message through the GRU: the message is
   ``s_self || s_other || f_edge || phi(dt)``, ``dt`` the time from the
   vertex's last memory update to the message, and ``phi`` either
   ``cos(omega dt + phase)`` or a lookup table over equal-frequency buckets;
2. per vertex, the chronologically last instance of the batch commits its
   updated memory, and its cached message is consumed;
3. embeddings, on the committed memory, from the ring buffer of the m_r
   most recent neighbours: the teacher attends over all of them (two heads,
   keys and values from neighbour memory, edge features and ``phi(dt)``);
   the student scores each slot from its ``dt`` alone (``a + W_t
   log1p(dt)``), keeps the top k by score (or, with the reservoir sampler,
   by a time-decayed hash priority), and averages the projected rows of
   those k by the softmax of their scores;
4. the last instance of each vertex caches the new message;
5. each edge enters both endpoints' ring buffers.

Every value is float32. ``precision`` is that of every matrix product:
``"highest"`` (float32 products) is the reference; ``"high"``, three
bfloat16 passes with float32 accumulation as the TPU's ``high`` precision
computes them, is the control, the step below the ``highest`` that the
configurations state. The three passes are spelt out here, so that the
control computes the same on any backend.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
HIGHEST = jax.lax.Precision.HIGHEST


def _contract(spec: str, a, b, precision: str):
    """``jnp.einsum(spec, a, b)`` in float32 at ``precision``: "highest",
    or "high": ``hi*hi + hi*lo + lo*hi`` of each operand's bfloat16 head
    ``hi`` and bfloat16 rest ``lo``, accumulated in float32."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision != "high":
        raise ValueError(f"precision {precision!r}: 'highest' or 'high'")

    def split(x):
        # reduce_precision, not a round trip through bfloat16: a compiler
        # that allows excess precision may drop the round trip, and the
        # remainder with it (XLA on the TPU does)
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(x - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)

    def one(x, y):
        return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)
    (ah, al), (bh, bl) = split(a), split(b)
    return one(ah, bh) + (one(ah, bl) + one(al, bh))


def _mm(x, w, precision: str):
    """``x @ w`` at ``precision`` (``x`` of any rank, ``w`` a matrix)."""
    return _contract("...c,cd->...d", x, w, precision)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def lut_boundaries(gaps: np.ndarray, n_entries: int) -> np.ndarray:
    """``n_entries - 1`` equal-frequency bucket boundaries of ``gaps``,
    strictly increasing."""
    qs = np.linspace(0.0, 1.0, n_entries + 1)[1:-1]
    b = np.maximum.accumulate(np.quantile(np.asarray(gaps, np.float64), qs))
    for i in range(1, len(b)):
        if b[i] <= b[i - 1]:
            b[i] = b[i - 1] * (1 + 1e-6) + 1e-6
    return b.astype(np.float32)


def _dense(key, shape, scale=None):
    scale = 1.0 / math.sqrt(shape[0]) if scale is None else scale
    return jax.random.normal(key, shape, jnp.float32) * scale


def _params(key, kind: str, w: dict, f_edge: int, boundaries):
    """One parameter set, as the served model lays it out. ``kind`` is
    "student" (SAT attention, LUT time encoder) or "teacher" (two-head
    attention, cosine time encoder)."""
    m, t, d, mr = w["f_mem"], w["f_time"], w["f_emb"], w["m_r"]
    f_mail = 2 * m + f_edge + t
    d_kv = m + f_edge + t
    ks = iter(jax.random.split(key, 24))
    small = lambda shape: 0.1 * jax.random.normal(next(ks), shape)  # noqa
    p = {"gru": {"w_i": _dense(next(ks), (f_mail, 3 * m)),
                 "w_h": _dense(next(ks), (m, 3 * m)),
                 "b_i": small((3 * m,)), "b_h": small((3 * m,))},
         "link": {"w1": _dense(next(ks), (2 * d, d)), "b1": small((d,)),
                  "w2": _dense(next(ks), (d, 1)), "b2": small((1,))}}
    if kind == "student":
        p["time"] = {"boundaries": boundaries,
                     "table": jax.random.normal(next(ks),
                                                (w["lut_entries"], t))}
        # a recency prior over the slots (most recent first), spread wider
        # than one bfloat16 rounding of the scores can move them
        p["attn"] = {"feat": {},
                     "a": (jnp.linspace(0.6 * mr / 2, -0.6 * mr / 2, mr)
                           + 0.3 * jax.random.normal(next(ks), (mr,))),
                     "w_t": _dense(next(ks), (mr, mr), scale=0.02),
                     "w_v": _dense(next(ks), (d_kv, d)), "b_v": small((d,)),
                     "w_out": _dense(next(ks), (m + d, d)),
                     "b_out": small((d,))}
    else:
        p["time"] = {"omega": jnp.asarray(1.0 / 10.0 ** np.linspace(0, 9, t),
                                          jnp.float32),
                     "phi": small((t,))}
        p["attn"] = {"feat": {},
                     "w_q": _dense(next(ks), (m + t, d)), "b_q": small((d,)),
                     "w_k": _dense(next(ks), (d_kv, d)), "b_k": small((d,)),
                     "w_v": _dense(next(ks), (d_kv, d)), "b_v": small((d,)),
                     "w_out": _dense(next(ks), (m + d, d)),
                     "b_out": small((d,))}
    return p


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make_weights(key, kinds: tuple, widths: tuple, f_edge: int, boundaries):
    w = dict(widths)
    return {kind: _params(jax.random.fold_in(key, i), kind, w, f_edge,
                          boundaries) for i, kind in enumerate(kinds)}


def make_weights(seed: int, kinds, widths: dict, f_edge: int,
                 boundaries: np.ndarray) -> dict:
    """Every parameter set a configuration serves, ``{kind: params}``,
    made on the device in one jitted call from ``seed``."""
    key = jax.random.key(seed % (2 ** 32))
    return _make_weights(key, tuple(sorted(set(kinds))),
                         tuple(sorted(widths.items())), int(f_edge),
                         jnp.asarray(boundaries))


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------


def init_state(n_nodes: int, w: dict, f_edge: int) -> dict:
    m, mr = w["f_mem"], w["m_r"]
    return {"memory": jnp.zeros((n_nodes, m), jnp.float32),
            "last_update": jnp.zeros((n_nodes,), jnp.float32),
            "mail": jnp.zeros((n_nodes, 2 * m + f_edge), jnp.float32),
            "mail_ts": jnp.zeros((n_nodes,), jnp.float32),
            "mail_valid": jnp.zeros((n_nodes,), bool),
            "nbr_ids": jnp.zeros((n_nodes, mr), jnp.int32),
            "nbr_ts": jnp.full((n_nodes, mr), -1.0, jnp.float32),
            "nbr_eid": jnp.zeros((n_nodes, mr), jnp.int32),
            "cursor": jnp.zeros((n_nodes,), jnp.int32)}


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------


def _lut(time_p, dt):
    bucket = jnp.sum(dt[..., None] >= time_p["boundaries"], axis=-1)
    return time_p["table"][bucket]


def _cos(time_p, dt):
    return jnp.cos(dt[..., None] * time_p["omega"]
                   + time_p["phi"])


def _phi(p, kind, dt):
    return (_lut(p["time"], dt) if kind == "student"
            else _cos(p["time"], dt))


def _masked_softmax(x, valid):
    x = jnp.where(valid, x, NEG)
    e = jnp.exp(x - jnp.max(x, axis=-1, keepdims=True)) * valid
    z = jnp.sum(e, axis=-1, keepdims=True)
    return jnp.where(z > 0, e / jnp.maximum(z, 1e-30), 0.0)


def _hash_uniform(eid, vids, t):
    """The reservoir sampler's stateless draw in (0, 1) per (vertex, slot):
    an integer hash of the slot's edge id, the queried vertex and the bits
    of the query time (the sampler's definition)."""
    h = eid.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
    h = h ^ (vids.astype(jnp.uint32)[:, None] * jnp.uint32(0x85EBCA77))
    tb = jax.lax.bitcast_convert_type(t.astype(jnp.float32), jnp.uint32)
    h = h ^ (tb[:, None] * jnp.uint32(0xC2B2AE3D))
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x2C1B3C6D)
    h = h ^ (h >> 12)
    h = h * jnp.uint32(0x297A2D39)
    h = h ^ (h >> 15)
    return ((h >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
            + jnp.float32(2.0 ** -25))


def _last_of_vertex(vids, valid):
    """True where an instance is the last valid one of its vertex in
    chronological order (edge e's source, then its destination)."""
    n = vids.shape[0]
    B = n // 2
    order = jnp.concatenate([2 * jnp.arange(B), 2 * jnp.arange(B) + 1])
    later = ((vids[None, :] == vids[:, None]) & valid[None, :]
             & (order[None, :] > order[:, None]))
    return valid & ~jnp.any(later, axis=1)


def _neighbours(st, vids, t):
    """Ring buffers of ``vids``, most recent first, and each slot's dt."""
    mr = st["nbr_ids"].shape[1]
    slot = (st["cursor"][vids][:, None] - 1 - jnp.arange(mr)) % mr
    ids = jnp.take_along_axis(st["nbr_ids"][vids], slot, axis=1)
    ts = jnp.take_along_axis(st["nbr_ts"][vids], slot, axis=1)
    eid = jnp.take_along_axis(st["nbr_eid"][vids], slot, axis=1)
    valid = ts >= 0.0
    dt = jnp.where(valid, jnp.maximum(t[:, None] - ts, 0.0), 0.0)
    return ids, eid, dt, valid


def _embed_student(p, lane, st, ef, vids, t, precision):
    ids, eid, dt, valid = _neighbours(st, vids, t)
    a = p["attn"]
    score = a["a"] + _mm(jnp.log1p(dt), a["w_t"].T, precision)
    k = min(lane["k"], dt.shape[1])
    if lane["sampler"] == "reservoir":
        u = _hash_uniform(eid, vids, t)
        key = jnp.log(u) * jnp.exp(jnp.minimum(dt / lane["tau"], 50.0))
    else:
        key = score
    _, idx = jax.lax.top_k(jnp.where(valid, key, NEG), k)
    take = lambda x: jnp.take_along_axis(x, idx, axis=1)  # noqa: E731
    s_valid = take(valid)
    w = _masked_softmax(take(score), s_valid)
    mem = st["memory"][take(ids)] * s_valid[..., None]
    edge = ef[take(eid)] * s_valid[..., None]
    kv = jnp.concatenate([mem, edge, _phi(p, "student", take(dt))], axis=-1)
    v = _mm(kv, a["w_v"], precision) + a["b_v"]
    agg = _contract("bn,bnd->bd", w, v, precision)
    h = _mm(jnp.concatenate([st["memory"][vids], agg], axis=-1), a["w_out"],
            precision)
    return h + a["b_out"]


def _embed_teacher(p, lane, st, ef, vids, t, precision):
    ids, eid, dt, valid = _neighbours(st, vids, t)
    a, n = p["attn"], vids.shape[0]
    heads = lane["heads"]
    mem = st["memory"][ids] * valid[..., None]
    edge = ef[eid] * valid[..., None]
    s_self = st["memory"][vids]
    q_in = jnp.concatenate([s_self, _cos(p["time"], jnp.zeros_like(t))],
                           axis=-1)
    q = (_mm(q_in, a["w_q"], precision) + a["b_q"]).reshape(n, heads, -1)
    kv = jnp.concatenate([mem, edge, _cos(p["time"], dt)], axis=-1)
    k = (_mm(kv, a["w_k"], precision) + a["b_k"]).reshape(
        n, dt.shape[1], heads, -1)
    v = (_mm(kv, a["w_v"], precision) + a["b_v"]).reshape(
        n, dt.shape[1], heads, -1)
    score = (_contract("bhd,bnhd->bhn", q, k, precision)
             / math.sqrt(q.shape[-1]))
    w = _masked_softmax(score, valid[:, None, :])
    agg = _contract("bhn,bnhd->bhd", w, v, precision).reshape(n, -1)
    h = _mm(jnp.concatenate([s_self, agg], axis=-1), a["w_out"], precision)
    return h + a["b_out"]


def step(p, lane: dict, st: dict, ef, batch, precision: str = "highest"):
    """One chronological batch ``(src, dst, eid, ts, valid)`` of one
    tenant. Returns ``(new state, embeddings (2B, f_emb))``: sources' rows
    first, then destinations'. Rows with ``valid`` False change nothing."""
    src, dst, eid, ts, valid = batch
    B = src.shape[0]
    V = st["memory"].shape[0]
    vids = jnp.concatenate([src, dst])
    t = jnp.concatenate([ts, ts])
    vv = jnp.concatenate([valid, valid])
    f_mem = st["memory"].shape[1]

    # 1. consume the cached message
    s = st["memory"][vids]
    mail_ok = st["mail_valid"][vids]
    dt = st["mail_ts"][vids] - st["last_update"][vids]
    msg = jnp.concatenate([st["mail"][vids],
                           _phi(p, lane["kind"], dt)], axis=-1)
    g = p["gru"]
    gi = _mm(msg, g["w_i"], precision) + g["b_i"]
    gh = _mm(s, g["w_h"], precision) + g["b_h"]
    r = jax.nn.sigmoid(gi[:, :f_mem] + gh[:, :f_mem])
    z = jax.nn.sigmoid(gi[:, f_mem:2 * f_mem] + gh[:, f_mem:2 * f_mem])
    cand = jnp.tanh(gi[:, 2 * f_mem:] + r * gh[:, 2 * f_mem:])
    s_new = jnp.where(mail_ok[:, None], (1 - z) * cand + z * s, s)
    lu_new = jnp.where(mail_ok, st["mail_ts"][vids], st["last_update"][vids])

    # 2. the last instance of each vertex commits
    last = _last_of_vertex(vids, vv)
    to = jnp.where(last, vids, V)                        # V: dropped
    st = dict(st)
    st["memory"] = st["memory"].at[to].set(s_new, mode="drop")
    st["last_update"] = st["last_update"].at[to].set(lu_new, mode="drop")

    # 3. embeddings on the committed memory
    embed = _embed_student if lane["kind"] == "student" else _embed_teacher
    h = embed(p, lane, st, ef, vids, t, precision)

    # 4. cache the new messages
    mem = st["memory"]
    e = ef[eid]
    mail = jnp.concatenate([
        jnp.concatenate([mem[src], mem[dst], e], axis=-1),
        jnp.concatenate([mem[dst], mem[src], e], axis=-1)])
    st["mail"] = st["mail"].at[to].set(mail, mode="drop")
    st["mail_ts"] = st["mail_ts"].at[to].set(t, mode="drop")
    st["mail_valid"] = st["mail_valid"].at[to].set(True, mode="drop")

    # 5. ring buffers: edge e lands after every earlier instance of the
    # same vertex in the batch
    order = jnp.concatenate([2 * jnp.arange(B), 2 * jnp.arange(B) + 1])
    earlier = jnp.sum((vids[None, :] == vids[:, None]) & vv[None, :]
                      & (order[None, :] < order[:, None]), axis=1)
    mr = st["nbr_ids"].shape[1]
    slot = (st["cursor"][vids] + earlier) % mr
    row = jnp.where(vv, vids, V)
    st["nbr_ids"] = st["nbr_ids"].at[row, slot].set(
        jnp.concatenate([dst, src]), mode="drop")
    st["nbr_ts"] = st["nbr_ts"].at[row, slot].set(t, mode="drop")
    st["nbr_eid"] = st["nbr_eid"].at[row, slot].set(
        jnp.concatenate([eid, eid]), mode="drop")
    st["cursor"] = (st["cursor"] + jnp.zeros((V,), jnp.int32).at[row].add(
        1, mode="drop")) % (2 ** 30)
    return st, h


def selection_margin(p, lane: dict, st: dict, batch) -> jax.Array:
    """Per vertex instance, how far the k-th kept neighbour's score lies
    above the best one left out, less what one bfloat16 rounding of the
    score's inputs can move it: a wide allowance for a float32 program, so
    that a row it keeps (positive margin) is decided at the served
    precision and at the control's. +inf where nothing is left out or the
    lane keeps every slot, or selects by the hash priority."""
    src, dst, eid, ts, valid = batch
    vids = jnp.concatenate([src, dst])
    t = jnp.concatenate([ts, ts])
    n_inf = jnp.full(vids.shape, jnp.inf)
    if lane["kind"] != "student" or lane["sampler"] == "reservoir":
        return n_inf
    _, _, dt, ok = _neighbours(st, vids, t)
    k = lane["k"]
    if k >= dt.shape[1]:
        return n_inf
    a = p["attn"]
    x = jnp.log1p(dt)
    score = jnp.where(ok, a["a"] + _mm(x, a["w_t"].T, "highest"), NEG)
    top, _ = jax.lax.top_k(score, k + 1)
    # one pass of bfloat16 rounds each input of x @ w_t by 2^-9 relative
    slack = 2.0 ** -7 * _mm(jnp.abs(x), jnp.abs(a["w_t"]).T, "highest")
    gap = top[:, k - 1] - top[:, k] - 2 * jnp.max(slack, axis=1)
    return jnp.where(jnp.sum(ok, axis=1) > k, gap, jnp.inf)
