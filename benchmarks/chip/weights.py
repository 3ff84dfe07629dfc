"""Weights drawn from ``--seed``, one leaf at a time by name and layer.

Every leaf is a function of (seed, leaf name, layer index) only, so the
served model and the plain reference draw the same numbers without
either handing the other anything: the harness maps the program's
parameter template through ``draw``, and the reference calls ``draw`` for
the leaves it knows by name, one layer at a time.

A stacked leaf whose axes after ``layers`` begin with ``experts`` is drawn
one expert at a time (``draw_expert``): expert ``i`` from the layer's key
folded with ``i``, its fan in that of one expert. A chip that holds
experts ``0..n-1`` of a layer then holds exactly the first ``n`` experts
of the uncut model, whatever ``n`` is.

Kinds of leaf (the ``init`` of a template entry):
  normal  truncated normal in [-2, 2], scaled by 1/sqrt(fan in) of one layer
  embed   normal, standard deviation 0.02
  ones, zeros
  ssm_a   log of uniform [1, 16] (Mamba-2's A_log)
"""
from __future__ import annotations

import math
import zlib
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of any size (seeds may pass 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    while True:
        key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
        seed >>= 32
        if not seed:
            return key


def leaf_key(key: jax.Array, name: str) -> jax.Array:
    # crc32, not hash(): str hashing is salted per process
    return jax.random.fold_in(key, np.uint32(zlib.crc32(name.encode())))


def draw(key: jax.Array, shape: Tuple[int, ...], init: str) -> jax.Array:
    """One layer's leaf in float32."""
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "ssm_a":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if init == "embed":
        return jax.random.normal(key, shape, jnp.float32) * 0.02
    if init in ("normal", "scaled"):
        fan_in = int(np.prod(shape[:-1])) if len(shape) > 1 else shape[0]
        scale = 1.0 / math.sqrt(max(1, fan_in))
        return jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * scale
    raise ValueError(f"unknown init {init!r}")


def draw_layer(key: jax.Array, name: str, layer, shape, init: str) -> jax.Array:
    """Layer ``layer`` (an int or a traced scalar) of stacked leaf ``name``."""
    return draw(jax.random.fold_in(leaf_key(key, name), layer), tuple(shape), init)


def draw_whole(key: jax.Array, name: str, shape, init: str) -> jax.Array:
    """A leaf that is not stacked over layers."""
    return draw(leaf_key(key, name), tuple(shape), init)


def draw_expert(key: jax.Array, name: str, layer, expert, shape, init: str) -> jax.Array:
    """Expert ``expert`` of layer ``layer`` (ints or traced scalars) of the
    stacked expert leaf ``name``; ``shape`` is one expert's, without the
    experts axis, so the fan in is one expert's."""
    key = jax.random.fold_in(jax.random.fold_in(leaf_key(key, name), layer), expert)
    return draw(key, tuple(shape), init)


def _draw_experts(key, name, layer, shape, init, dt):
    """All experts of one layer of an expert leaf, ``shape`` = (E, ...)."""
    return jax.lax.map(lambda e: draw_expert(key, name, layer, e, shape[1:], init).astype(dt),
                       jnp.arange(shape[0]))


def _leaves(template, is_leaf):
    out = []
    for path, spec in jax.tree_util.tree_flatten_with_path(template, is_leaf=is_leaf)[0]:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out.append((name, spec))
    return out


def params_fn(template, default_dtype: str, *, is_leaf):
    """``key -> parameter tree`` for ``template`` (entries with ``shape``,
    ``axes``, ``init``, ``dtype``), each leaf in the type it is served in.
    Layers of a stacked leaf are drawn one after another, so no leaf is
    held in float32 whole."""
    leaves = _leaves(template, is_leaf)
    treedef = jax.tree_util.tree_structure(template, is_leaf=is_leaf)

    def make(key):
        out = []
        for name, spec in leaves:
            dt = jnp.dtype(spec.dtype or default_dtype)
            if tuple(spec.axes[:2]) == ("layers", "experts"):
                arr = jax.lax.map(
                    lambda l: _draw_experts(key, name, l, spec.shape[1:], spec.init, dt),
                    jnp.arange(spec.shape[0]))
            elif spec.axes and spec.axes[0] == "layers":
                n, shape = spec.shape[0], spec.shape[1:]
                arr = jax.lax.map(
                    lambda l: draw_layer(key, name, l, shape, spec.init).astype(dt),
                    jnp.arange(n))
            else:
                arr = draw_whole(key, name, spec.shape, spec.init).astype(dt)
            out.append(arr)
        return jax.tree_util.tree_unflatten(treedef, out)

    return make


def make_params(template, default_dtype: str, seed: int, *, is_leaf):
    """The whole parameter tree, made on the default device in one jitted
    call from ``seed``."""
    return jax.jit(params_fn(template, default_dtype, is_leaf=is_leaf))(seed_key(seed))
