"""Weights from the seed, made by the benchmark and not by the program:
the plumbing every family shares. A family (``bench/families/
<family>.py``) draws its weights at the published shapes
(``published(key, model)``) and lays them out as the program's
parameter tree (``to_program(w, model, cfg)``); here they are made from
the seed in one jitted call on the device, and the tree is checked leaf
by leaf against the one the program's own ``init_params`` declares.
The layout helpers place published heads into the program's padded head
grid (``ModelConfig.padded_heads``) with zeros in the padding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def trunc(key, shape, fan_in):
    """Truncated normal (+-3 sd) with sd = fan_in ** -0.5, float32."""
    return fan_in ** -0.5 * jax.random.truncated_normal(
        key, -3.0, 3.0, shape, jnp.float32)


def pad_q(x, axis: int, kv: int, g: int, kvp: int, gp: int):
    """Query heads (axis of size kv*g, head h = i*g + j) -> the padded
    (kvp*gp) grid, head (i, j) at slot i*gp + j, zeros elsewhere."""
    shp = x.shape
    x = x.reshape(shp[:axis] + (kv, g) + shp[axis + 1:])
    pad = [(0, 0)] * x.ndim
    pad[axis], pad[axis + 1] = (0, kvp - kv), (0, gp - g)
    x = jnp.pad(x, pad)
    return x.reshape(shp[:axis] + (kvp * gp,) + shp[axis + 1:])


def pad_axis(x, axis: int, size: int):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, pad)


def check_layout(params, cfg) -> None:
    """Raise unless ``params`` has exactly the tree, shapes and dtypes of
    the program's own ``init_params`` for ``cfg``."""
    from repro.models import transformer as T
    want = jax.eval_shape(lambda k: T.init_params(k, cfg),
                          jax.random.key(0))
    got = jax.eval_shape(lambda p: p, params)
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError(f"parameter tree differs from the program's: "
                         f"{jax.tree.structure(got)} vs "
                         f"{jax.tree.structure(want)}")
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"leaf {a.shape}/{a.dtype} where the program "
                             f"declares {b.shape}/{b.dtype}")


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits (the low word keys, the
    high word is folded in)."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def make_program_params(seed: int, m: dict, cfg, family):
    """One jitted call: seed -> the family's published weights laid out
    as the program's parameter tree, on device."""
    key = seed_key(seed)

    def build(k):
        return family.to_program(family.published(k, m), m, cfg)

    check_layout(jax.eval_shape(build, key), cfg)
    return jax.block_until_ready(jax.jit(build)(key))


def make_published(seed: int, m: dict, published):
    """One jitted call: seed -> ``published(key, m)``, on device."""
    return jax.block_until_ready(
        jax.jit(lambda k: published(k, m))(seed_key(seed)))
