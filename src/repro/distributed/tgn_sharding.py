"""Sharding rules for TGN vertex state: the paper's banks, as mesh axes.

The accelerator keeps its Graph Storage in banked BRAM partitions so the
MUU/EU pipelines can hit many vertices per cycle (§IV-A). Our jax analogue
of "more banks" is placing the multi-tenant SessionManager's stacked
``(tenant, V, ...)`` VertexState tables on a ``jax.sharding.Mesh``:

  * ``tenant`` axis — the shard axis of the stacked tables and of every
    padded batch input: each device advances its slice of the fleet, and
    because the vmapped step has no cross-tenant reduction the partitioned
    launch is BITWISE-identical to the single-device one;
  * ``vertex``  axis — optional second axis splitting the V dimension of
    each tenant's tables (memory, mailbox, ring buffers), the direct
    analogue of the paper's vertex-id bank interleaving. Gathers/scatters
    across it become collective transfers XLA inserts; numerics unchanged.

This module is the rule table mapping the ``VertexState`` pytree (single
or tenant-stacked), the padded batch tuples, and the ``BatchOut`` result
to PartitionSpecs — the same first-match-wins pattern as the parameter
rules in ``distributed/sharding.py``. Axes that do not divide a dimension
are dropped (replicated) rather than rejected, so one rule table serves
any mesh shape; ``serving/cluster.py`` consumes these specs.
"""
from __future__ import annotations

import math
import re
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import mailbox, tgn

PyTree = Any

TENANT_AXIS = "tenant"
VERTEX_AXIS = "vertex"

# (regex on VertexState field name, spec for the UNSTACKED leaf, V leading).
# First match wins; a stacked (tenant, V, ...) leaf left-pads TENANT_AXIS.
STATE_RULES = [
    # 2-D tables: (V, f_mem) memory, (V, f_mail_raw) mail,
    # (V, m_r) ring buffers — V over the vertex axis, feature dims local
    (r"^(memory|mail|nbr_ids|nbr_ts|nbr_eid)$", P(VERTEX_AXIS, None)),
    # 1-D per-vertex scalars
    (r"^(last_update|mail_ts|mail_valid|nbr_cursor)$", P(VERTEX_AXIS)),
    (r".*", P()),
]

_FIELDS = mailbox.VertexState._fields


def _axis_size(mesh: Mesh, name: str) -> int:
    return int(mesh.shape.get(name, 1))


def _fit_axes(spec: P, shape, mesh: Mesh) -> P:
    """Drop spec axes absent from the mesh or not dividing their dim
    (same degrade-to-replicated policy as sharding._validate)."""
    entries = list(tuple(spec) + (None,) * (len(shape) - len(spec)))
    for i, (dim, ax) in enumerate(zip(shape, entries)):
        if ax is None:
            continue
        n = _axis_size(mesh, ax)
        if n <= 1 or dim % n != 0:
            entries[i] = None
    return P(*entries)


def _field_spec(field: str) -> P:
    for pat, spec in STATE_RULES:
        if re.match(pat, field):
            return spec
    raise AssertionError("unreachable")


def _tenant_axis(mesh: Mesh):
    """The tenant shard axis, or None on a mesh without one (vertex-only
    meshes replicate the tenant dim)."""
    return TENANT_AXIS if TENANT_AXIS in mesh.axis_names else None


def state_specs(mesh: Mesh, state_like: mailbox.VertexState, *,
                stacked: bool = True) -> mailbox.VertexState:
    """PartitionSpec pytree for a VertexState of UNSTACKED leaves (arrays
    or ShapeDtypeStructs, V leading).

    ``stacked=True``: specs describe leaves carrying a leading tenant dim
    ``(T, V, ...)`` sharded over ``tenant`` (T is always a capacity —
    a multiple of the axis size); the V dim additionally shards over
    ``vertex`` when that axis exists and divides.
    """
    out = []
    for field, leaf in zip(_FIELDS, state_like):
        spec = _fit_axes(_field_spec(field), leaf.shape, mesh)
        if stacked:
            spec = P(_tenant_axis(mesh), *tuple(spec))
        out.append(spec)
    return mailbox.VertexState(*out)


def batch_specs(mesh: Mesh) -> tuple:
    """Specs for the stacked padded batch tuple: five (T, B) arrays
    (src, dst, eid, ts, valid), row-sharded over the tenant axis."""
    return tuple(P(_tenant_axis(mesh), None) for _ in range(5))


def out_specs(mesh: Mesh, state_like: mailbox.VertexState) -> tgn.BatchOut:
    """Specs for the cohort launch's BatchOut: the committed stacked state
    keeps its input layout, every per-tenant output is tenant-sharded on
    its leading axis."""
    t = P(_tenant_axis(mesh))
    return tgn.BatchOut(state=state_specs(mesh, state_like, stacked=True),
                        emb_src=t, emb_dst=t, attn_logits=t,
                        nbr_valid=t, nbr_dt=t)


def tenant_map(mesh: Mesh):
    """Wrapper for a vmapped cohort step ``f(params, state, batch,
    edge_feats, node_feats) -> BatchOut``: run it under ``shard_map``,
    each device stepping its own block of the tenant axis.

    A Mosaic kernel cannot be partitioned by the compiler, so the step
    must see per-device arrays. Every mesh axis other than ``tenant``
    sees whole tables: a vertex-sharded state is gathered for the step
    and re-sharded after it."""
    t = P(_tenant_axis(mesh))

    def wrap(f):
        return jax.shard_map(f, mesh=mesh, in_specs=(P(), t, t, P(), P()),
                             out_specs=t, check_vma=False)

    return wrap


def replicated(mesh: Mesh) -> NamedSharding:
    """The placement of cohort-shared operands (params, edge/node feature
    stores): one full copy per device."""
    return NamedSharding(mesh, P())


def make_shardings(mesh: Mesh, spec_tree: PyTree) -> PyTree:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def tenant_capacity(n_tenants: int, mesh: Mesh) -> int:
    """Stacked-table rows for ``n_tenants``: the smallest multiple of the
    tenant-axis size that fits them (pad slots are idle-masked)."""
    n = max(1, _axis_size(mesh, TENANT_AXIS))
    return max(n, n * math.ceil(n_tenants / n))


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------


def make_tenant_mesh(spec: str | int | None = None, *,
                     devices=None) -> Mesh:
    """Build the tenant fabric's device mesh from a CLI-style spec.

    ``spec``: ``None``/``""`` (all devices on the tenant axis), an int or
    numeric string (``"8"`` — tenant axis of that size), or an explicit
    ``"tenant=4,vertex=2"`` assignment. Axis order follows the spec; only
    ``tenant`` and ``vertex`` are meaningful to the state rules above.
    """
    devices = list(jax.devices() if devices is None else devices)
    if spec is None or spec == "":
        sizes = {TENANT_AXIS: len(devices)}
    elif isinstance(spec, int) or str(spec).isdigit():
        sizes = {TENANT_AXIS: int(spec)}
    else:
        sizes = {}
        for clause in str(spec).split(","):
            if "=" not in clause:
                raise ValueError(
                    f"bad mesh clause {clause!r} in {spec!r}; expected "
                    "'<axis>=<size>[,...]' e.g. 'tenant=4,vertex=2'")
            name, _, size = clause.partition("=")
            name = name.strip()
            if name in sizes:
                raise ValueError(f"duplicate mesh axis {name!r} in {spec!r}")
            if not size.strip().isdigit() or int(size) < 1:
                raise ValueError(f"bad size for mesh axis {name!r} in "
                                 f"{spec!r}")
            sizes[name] = int(size)
    n = 1
    for s in sizes.values():
        n *= s
    if n > len(devices):
        raise RuntimeError(
            f"mesh {sizes} needs {n} devices, found {len(devices)} — on a "
            "CPU host run under XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={n} (make test-sharded does), or shrink the mesh")
    arr = np.asarray(devices[:n]).reshape(tuple(sizes.values()))
    return Mesh(arr, tuple(sizes))
