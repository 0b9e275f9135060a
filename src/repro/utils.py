"""Small shared utilities: parameter init, padding, tree helpers.

The framework is flax-free: parameters are nested dicts of jnp arrays,
models are pure functions ``apply(params, ...)`` with ``init(rng, cfg)``
constructors. This keeps every layer pjit/shard_map friendly and makes
sharding rules a pure function of the parameter tree path.
"""
from __future__ import annotations

import dataclasses
import math
import os
import zlib
from typing import Any, Callable, Iterable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

#: the checkout this package was loaded from (``<checkout>/src/repro``)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX reads it:
    nothing here names another directory. Otherwise the cache lives at
    ``<checkout>/.jax_cache``, a fixed path (the path is part of the cache
    key, so a directory that moves never hits). Call it from a program's
    entry point, never at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

# ---------------------------------------------------------------------------
# RNG helpers
# ---------------------------------------------------------------------------


def rng_seq(key: jax.Array):
    """Infinite stream of fresh PRNG keys from a root key."""
    while True:
        key, sub = jax.random.split(key)
        yield sub


def fold_path(key: jax.Array, path: str) -> jax.Array:
    """Deterministic per-path key derivation (stable across refactors AND
    processes — crc32, not the per-process-salted builtin hash)."""
    h = np.uint32(zlib.crc32(path.encode()) % (2**32 - 1))
    return jax.random.fold_in(key, h)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key: jax.Array, shape: Sequence[int], *, scale: float | None = None,
               dtype=jnp.float32) -> jax.Array:
    """LeCun-normal style init for dense kernels: (fan_in, fan_out...)."""
    fan_in = shape[0]
    if scale is None:
        scale = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, tuple(shape)) * scale).astype(dtype)


def embed_init(key: jax.Array, shape: Sequence[int], *, dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(key, tuple(shape)) * 0.02).astype(dtype)


def zeros(shape: Sequence[int], dtype=jnp.float32) -> jax.Array:
    return jnp.zeros(tuple(shape), dtype=dtype)


def ones(shape: Sequence[int], dtype=jnp.float32) -> jax.Array:
    return jnp.ones(tuple(shape), dtype=dtype)


# ---------------------------------------------------------------------------
# Shape / padding helpers (TPU lane alignment)
# ---------------------------------------------------------------------------

LANE = 128  # MXU/VPU lane width on TPU

#: Masking value for invalid attention logits — the single source of truth
#: shared by the jnp reference path (core/pruning.py) and every Pallas
#: kernel (kernels/sat_aggregate.py, kernels/fused_step.py, kernels/ref.py).
#: A drift between the reference and kernel values would silently break the
#: fused-vs-staged numeric equivalence the kernel tests pin, so nobody may
#: define a private copy.
NEG_INF = -1e30


def round_up(x: int, m: int = LANE) -> int:
    return ((x + m - 1) // m) * m


def pad_axis(x: jax.Array, axis: int, target: int) -> jax.Array:
    """Zero-pad ``axis`` of x up to length ``target``."""
    cur = x.shape[axis]
    if cur == target:
        return x
    if cur > target:
        raise ValueError(f"cannot pad axis {axis} from {cur} down to {target}")
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - cur)
    return jnp.pad(x, pads)


def pad_to_lanes(x: jax.Array, axis: int = -1, m: int = LANE) -> jax.Array:
    axis = axis % x.ndim
    return pad_axis(x, axis, round_up(x.shape[axis], m))


# ---------------------------------------------------------------------------
# Tree helpers
# ---------------------------------------------------------------------------


def tree_size(tree: PyTree) -> int:
    """Total number of parameters in a pytree."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def tree_bytes(tree: PyTree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def tree_cast(tree: PyTree, dtype) -> PyTree:
    return jax.tree.map(lambda x: x.astype(dtype)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def tree_paths(tree: PyTree) -> list[tuple[str, Any]]:
    """Flatten a tree to (dot.path, leaf) pairs."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = ".".join(_key_str(k) for k in path)
        out.append((name, leaf))
    return out


def _key_str(k) -> str:
    if isinstance(k, jax.tree_util.DictKey):
        return str(k.key)
    if isinstance(k, jax.tree_util.SequenceKey):
        return str(k.idx)
    if isinstance(k, jax.tree_util.GetAttrKey):
        return str(k.name)
    return str(k)


def assert_finite(tree: PyTree, name: str = "tree") -> None:
    for path, leaf in tree_paths(tree):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            if not bool(jnp.all(jnp.isfinite(leaf))):
                raise AssertionError(f"non-finite values in {name}.{path}")


# ---------------------------------------------------------------------------
# Config base
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FrozenConfig:
    """Base class for immutable configs with ``replace``/``asdict``."""

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)

    def asdict(self) -> dict:
        return dataclasses.asdict(self)
