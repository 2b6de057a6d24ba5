"""Parameter-spec system: one declaration → init / abstract tree / shardings.

Every layer declares its parameters as a tree of :class:`P` (shape + logical
axes + initializer).  From that single declaration we derive:

* ``init_from_spec``      — PRNG-keyed real initialization (smoke tests, examples),
* ``abstract_from_spec``  — ``jax.ShapeDtypeStruct`` tree with **no allocation**
                            (the multi-pod dry-run path),
* ``axes_from_spec``      — the logical-axes tree consumed by
                            :mod:`repro.sharding.partitioner`.

This is the t5x/flax-partitioning idea without the flax dependency, and it
guarantees the three trees can never drift structurally.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["P", "init_from_spec", "abstract_from_spec", "axes_from_spec",
           "count_params", "param_bytes"]


@dataclasses.dataclass(frozen=True)
class P:
    """Spec for one parameter tensor.

    ``axes`` are logical names, one per dim (None = never sharded), e.g.
    ``("embed", "q_heads", "head_dim")``.  ``init`` ∈ {normal, zeros, ones,
    fan_in, embed} or a callable ``(key, shape, dtype) -> array``.
    """

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: Any = "fan_in"
    scale: float = 1.0
    dtype: Any = None

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes} rank mismatch")


def _is_spec(x) -> bool:
    return isinstance(x, P)


def _init_one(key, p: P, dtype) -> jax.Array:
    dtype = p.dtype or dtype
    shape = p.shape
    if callable(p.init):
        return p.init(key, shape, dtype)
    if p.init == "zeros":
        return jnp.zeros(shape, dtype)
    if p.init == "ones":
        return jnp.ones(shape, dtype)
    if p.init == "normal":
        return (p.scale * jax.random.normal(key, shape)).astype(dtype)
    if p.init == "embed":
        return (p.scale * jax.random.normal(key, shape)).astype(dtype)
    if p.init == "fan_in":
        # truncated-normal with 1/sqrt(fan_in); fan_in = prod of all dims but last
        fan_in = max(1, int(np.prod(shape[:-1])))
        std = p.scale / np.sqrt(fan_in)
        return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)
    raise ValueError(f"unknown init {p.init!r}")


def init_from_spec(key, spec, dtype=jnp.float32):
    """Materialize real parameters from a spec tree.

    One jitted program writes every leaf exactly once.  Initialized
    eagerly, each initializer materializes its temporaries at the leaf's
    full size — a truncated normal of a stacked ``(40, 2048, 8192)`` fp32
    weight holds several 2.7 GB buffers — which overflows a 16 GB chip at
    published widths before serving starts.
    """
    leaves, treedef = jax.tree_util.tree_flatten(spec, is_leaf=_is_spec)
    vals = _init_leaves(key, tuple(leaves), jnp.dtype(dtype))
    return jax.tree_util.tree_unflatten(treedef, vals)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init_leaves(key, leaves, dtype):
    keys = jax.random.split(key, len(leaves))
    return [_init_one(k, p, dtype) for k, p in zip(keys, leaves)]


def abstract_from_spec(spec, dtype=jnp.float32):
    """ShapeDtypeStruct tree — zero allocation (dry-run path)."""
    return jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype or dtype),
        spec, is_leaf=_is_spec)


def axes_from_spec(spec):
    """Logical-axes tree (same structure, tuples of names)."""
    return jax.tree_util.tree_map(lambda p: p.axes, spec, is_leaf=_is_spec)


def count_params(spec) -> int:
    leaves = jax.tree_util.tree_leaves(spec, is_leaf=_is_spec)
    return int(sum(np.prod(p.shape) for p in leaves))


def param_bytes(spec, dtype=jnp.float32) -> int:
    leaves = jax.tree_util.tree_leaves(spec, is_leaf=_is_spec)
    return int(sum(np.prod(p.shape) * jnp.dtype(p.dtype or dtype).itemsize
                   for p in leaves))
