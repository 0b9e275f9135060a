"""The benchmark's traffic source: a seeded temporal-graph stream, each
tenant's replay of it, and the open-loop arrival schedule.

Everything here is NumPy and never imports JAX, so the load generator's
child process (``bench/loadgen.py``) can use it without touching the chip.
The graph generator is a copy of the program's synthetic stream (bipartite
user->item interactions, Zipf endpoint popularity, Pareto inter-event
gaps, 172-d edge features projected from endpoint latents), kept here so
that the yardstick stays fixed while the program changes. It also makes
streams over one vertex population (``bipartite: false``, actor->actor
events as in GDELT) and static node features (``f_feat > 0``), projected
from the vertices' latents as edge features are from the endpoints'.

A traffic mix is a JSON file of parameters (``bench/traffic/<name>.json``)
that this one generator reads:

    loop            "closed": every round carries each tenant's next batch,
                    as fast as the session's backpressure lets it (replay,
                    catch-up); "open": events sent on a seeded schedule
                    whatever the server does (independent users).
    rate_eps        open loop: mean offered events per second, all tenants.
    tenant_zipf_s   open loop: each event's tenant is drawn Zipf(s) over the
                    tenants (0 = uniform).
    burst           open loop, optional: {"period_s": P, "on_share": q};
                    arrivals come only in the first q of every period, at
                    rate_eps / q, so the mean rate stays rate_eps.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class GraphShape:
    """Shape of an interaction stream: bipartite user->item (a JODIE-style
    data set), or with ``bipartite`` False events between the ``n_users``
    vertices of one population (``n_items`` 0)."""
    n_users: int
    n_items: int
    n_edges: int
    f_edge: int = 172          # edge features, 0 for none
    latent: int = 16
    zipf_a: float = 1.2        # endpoint popularity skew
    pareto_a: float = 1.1      # inter-event time tail
    t_scale: float = 60.0      # scale of inter-event seconds
    noise: float = 0.3
    f_feat: int = 0            # static node features, 0 for none
    bipartite: bool = True

    def __post_init__(self):
        if not self.bipartite and self.n_items:
            raise ValueError("a stream over one population has n_items 0, "
                             f"got {self.n_items}")

    @property
    def n_nodes(self) -> int:
        return self.n_users + self.n_items


@dataclasses.dataclass
class Graph:
    """A chronological edge stream, its edge features and the vertices'
    static features (host NumPy)."""
    src: np.ndarray         # (E,) int32 user ids in [0, n_users)
    dst: np.ndarray         # (E,) int32 item ids in [n_users, n_nodes);
    #                         over one population, in [0, n_users) too
    ts: np.ndarray          # (E,) float32, non-decreasing
    edge_feats: np.ndarray  # (E, f_edge) float32
    shape: GraphShape
    node_feats: np.ndarray | None = None   # (n_nodes, f_feat) float32

    @property
    def n_edges(self) -> int:
        return int(self.src.shape[0])


def _zipf_choice(rng, n: int, size: int, a: float) -> np.ndarray:
    ranks = np.arange(1, n + 1, dtype=np.float64)
    p = ranks ** (-a)
    p /= p.sum()
    return rng.choice(n, size=size, p=p)


def generate(shape: GraphShape, seed: int) -> Graph:
    """The seeded stream: the same ``(shape, seed)`` gives the same graph.
    Node features come from a generator of their own, so a shape without
    them draws exactly what it drew before they existed."""
    rng = np.random.RandomState(seed % (2 ** 32))
    U, I, E = shape.n_users, shape.n_items, shape.n_edges
    zu = rng.randn(U, shape.latent).astype(np.float32) / np.sqrt(shape.latent)
    zi = rng.randn(I, shape.latent).astype(np.float32) / np.sqrt(shape.latent)
    src = _zipf_choice(rng, U, E, shape.zipf_a).astype(np.int32)
    # each source picks, among 8 Zipf candidates, the destination of
    # highest affinity: an item, or over one population another vertex (a
    # candidate equal to the source is drawn again)
    zd = zi if shape.bipartite else zu
    n_cand = 8
    cand = _zipf_choice(rng, len(zd), E * n_cand, shape.zipf_a).reshape(
        E, n_cand)
    while not shape.bipartite and (loop := cand == src[:, None]).any():
        cand[loop] = _zipf_choice(rng, U, int(loop.sum()), shape.zipf_a)
    aff = np.einsum("el,ecl->ec", zu[src], zd[cand])
    aff += shape.noise * rng.randn(E, n_cand).astype(np.float32)
    dst = cand[np.arange(E), np.argmax(aff, axis=1)].astype(np.int32)
    gaps = (rng.pareto(shape.pareto_a, size=E) + 1.0) * shape.t_scale
    ts = np.cumsum(gaps).astype(np.float32)
    proj = rng.randn(2 * shape.latent, shape.f_edge).astype(np.float32)
    proj /= np.sqrt(2 * shape.latent)
    lat = np.concatenate([zu[src], zd[dst]], axis=1)
    edge_feats = (lat @ proj + shape.noise * rng.randn(
        E, shape.f_edge).astype(np.float32)).astype(np.float32)
    if shape.bipartite:
        dst = (dst + U).astype(np.int32)
    return Graph(src=src, dst=dst, ts=ts, edge_feats=edge_feats, shape=shape,
                 node_feats=_node_feats(shape, seed,
                                        np.concatenate([zu, zi])))


def _node_feats(shape: GraphShape, seed: int, latents: np.ndarray):
    """``(n_nodes, f_feat)`` static features, a noisy projection of each
    vertex's latent, drawn apart from the stream's own numbers; None where
    the shape has none."""
    if not shape.f_feat:
        return None
    rng = np.random.RandomState([seed % (2 ** 32), 0x6E6F6465])  # "node"
    proj = rng.randn(shape.latent, shape.f_feat).astype(np.float32)
    proj /= np.sqrt(shape.latent)
    noise = rng.randn(latents.shape[0], shape.f_feat).astype(np.float32)
    return (latents @ proj + shape.noise * noise).astype(np.float32)


def vertex_gaps(graph: Graph) -> np.ndarray:
    """Seconds between consecutive interactions of the same vertex: the
    time deltas a time encoder sees in this stream."""
    ids = np.concatenate([graph.src, graph.dst])
    ts = np.concatenate([graph.ts, graph.ts]).astype(np.float64)
    order = np.lexsort((ts, ids))
    ids, ts = ids[order], ts[order]
    same = ids[1:] == ids[:-1]
    gaps = (ts[1:] - ts[:-1])[same]
    return gaps[gaps > 0]


class TenantReplay:
    """One tenant's replay of the stream: it starts at its own offset
    ``start`` and reads on in order; past the end it wraps to the start,
    with every lap's timestamps shifted by the stream's span, so that a
    tenant's timestamps never go back."""

    def __init__(self, graph: Graph, start: int):
        self.graph = graph
        self.start = int(start) % graph.n_edges
        self.taken = 0
        ts = graph.ts.astype(np.float64)
        # one lap of the stream plus one median gap
        self.span = float(ts[-1] - ts[0] + np.median(np.diff(ts)))
        self._t0 = float(ts[self.start])

    def take(self, n: int):
        """The next ``n`` events: ``(src, dst, eid, ts)`` host arrays."""
        E = self.graph.n_edges
        idx = self.start + self.taken + np.arange(n, dtype=np.int64)
        self.taken += n
        eid = (idx % E).astype(np.int32)
        ts = (self.graph.ts[eid].astype(np.float64) + (idx // E) * self.span
              - self._t0)
        return (self.graph.src[eid], self.graph.dst[eid], eid,
                ts.astype(np.float32))


def tenant_starts(graph: Graph, n_tenants: int) -> list:
    """Tenant ``i`` starts at the ``i``-th of ``n_tenants`` equal slices."""
    return [i * graph.n_edges // n_tenants for i in range(n_tenants)]


@dataclasses.dataclass
class Schedule:
    """An open-loop arrival schedule: event ``j`` is due ``due[j]`` seconds
    after the window opens and belongs to tenant ``tenant[j]``."""
    due: np.ndarray        # (N,) float64, non-decreasing
    tenant: np.ndarray     # (N,) int32
    src: np.ndarray        # (N,) int32
    dst: np.ndarray        # (N,) int32
    eid: np.ndarray        # (N,) int32
    ts: np.ndarray         # (N,) float32, non-decreasing per tenant


def arrivals(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times in ``[0, seconds)`` of a Poisson process at
    ``traffic["rate_eps"]``, modulated on/off by ``traffic["burst"]``."""
    rng = np.random.RandomState((seed * 7919 + 17) % (2 ** 32))
    rate = float(traffic["rate_eps"])
    burst = traffic.get("burst")
    on = float(burst["on_share"]) if burst else 1.0
    period = float(burst["period_s"]) if burst else 1.0
    # draw the on-time arrivals at rate/on, then map on-time onto the clock
    peak = rate / on
    n = int(rng.poisson(peak * seconds * on))
    on_t = np.sort(rng.uniform(0.0, seconds * on, size=n))
    if burst:
        k = np.floor(on_t / (period * on))
        on_t = k * period + (on_t - k * period * on)
    return on_t[on_t < seconds]


def schedule(traffic: dict, replays: list, seconds: float,
             seed: int) -> Schedule:
    """The open-loop schedule of one run: due times from ``arrivals``,
    each event's tenant drawn Zipf(``tenant_zipf_s``) over the tenants,
    and each tenant's events read on, in order, from its own replay."""
    due = arrivals(traffic, seconds, seed)
    n_tenants = len(replays)
    rng = np.random.RandomState((seed * 104729 + 3) % (2 ** 32))
    s = float(traffic.get("tenant_zipf_s", 0.0))
    p = np.arange(1, n_tenants + 1, dtype=np.float64) ** (-s)
    tenant = rng.choice(n_tenants, size=due.shape[0],
                        p=p / p.sum()).astype(np.int32)
    n = due.shape[0]
    src = np.empty(n, np.int32)
    dst = np.empty(n, np.int32)
    eid = np.empty(n, np.int32)
    ts = np.empty(n, np.float32)
    for t, replay in enumerate(replays):
        rows = np.nonzero(tenant == t)[0]
        if rows.size:
            src[rows], dst[rows], eid[rows], ts[rows] = replay.take(rows.size)
    return Schedule(due=due, tenant=tenant, src=src, dst=dst, eid=eid, ts=ts)
