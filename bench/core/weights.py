"""Weights from the seed, made by the benchmark and not by the program.

``published`` draws a decoder's weights at the published shapes (heads as
published, no padding) in one jitted call on the device. ``to_program``
lays the same arrays out as the program's parameter tree: stacked over
layers, query/key/value heads placed into the program's padded head grid
(``ModelConfig.padded_heads``) with zeros in the padding, and checked
leaf by leaf against the tree the program's own ``init_params`` declares.
The plain reference (``reference.py``) regenerates the published arrays
from the same seed, so it shares no array with the program.

Draws: embeddings N(0, 0.02); matrices truncated normal (+-3 sd) with
sd = fan_in ** -0.5; QKV biases N(0, 0.1) where the model has them; norm
scales 1. All float32, the type the program stores and serves weights
in (it casts to bfloat16 at each use).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BIAS_SD = 0.1


def dims(m: dict) -> dict:
    """Published sizes under short names."""
    h = m["num_attention_heads"]
    d = m["hidden_size"]
    return {"L": m["num_hidden_layers"], "D": d, "H": h,
            "KV": m["num_key_value_heads"],
            "hd": m.get("head_dim") or d // h,
            "F": m["intermediate_size"], "V": m["vocab_size"],
            "tied": bool(m["tie_word_embeddings"]),
            "bias": bool(m.get("attention_bias", False)),
            "eps": float(m["rms_norm_eps"]),
            "theta": float(m["rope_theta"])}


def _trunc(key, shape, fan_in):
    return fan_in ** -0.5 * jax.random.truncated_normal(
        key, -3.0, 3.0, shape, jnp.float32)


def published(key, m: dict) -> dict:
    """Published-shape weights, stacked over layers (leading axis L)."""
    n = dims(m)
    L, D, H, KV, hd, F, V = (n[k] for k in ("L", "D", "H", "KV", "hd",
                                            "F", "V"))
    ks = iter(jax.random.split(key, 12))
    w = {"embed": 0.02 * jax.random.normal(next(ks), (V, D), jnp.float32),
         "final_norm": jnp.ones((D,), jnp.float32),
         "norm1": jnp.ones((L, D), jnp.float32),
         "norm2": jnp.ones((L, D), jnp.float32),
         "wq": _trunc(next(ks), (L, D, H, hd), D),
         "wk": _trunc(next(ks), (L, D, KV, hd), D),
         "wv": _trunc(next(ks), (L, D, KV, hd), D),
         "wo": _trunc(next(ks), (L, H, hd, D), H * hd),
         "w_gate": _trunc(next(ks), (L, D, F), D),
         "w_up": _trunc(next(ks), (L, D, F), D),
         "w_down": _trunc(next(ks), (L, F, D), F)}
    if not n["tied"]:
        w["lm_head"] = 0.02 * jax.random.normal(next(ks), (D, V),
                                                jnp.float32)
    if n["bias"]:
        w["bq"] = BIAS_SD * jax.random.normal(next(ks), (L, H, hd))
        w["bk"] = BIAS_SD * jax.random.normal(next(ks), (L, KV, hd))
        w["bv"] = BIAS_SD * jax.random.normal(next(ks), (L, KV, hd))
    return w


def _pad_q(x, axis: int, kv: int, g: int, kvp: int, gp: int):
    """Query heads (axis of size kv*g, head h = i*g + j) -> the padded
    (kvp*gp) grid, head (i, j) at slot i*gp + j, zeros elsewhere."""
    shp = x.shape
    x = x.reshape(shp[:axis] + (kv, g) + shp[axis + 1:])
    pad = [(0, 0)] * x.ndim
    pad[axis], pad[axis + 1] = (0, kvp - kv), (0, gp - g)
    x = jnp.pad(x, pad)
    return x.reshape(shp[:axis] + (kvp * gp,) + shp[axis + 1:])


def _pad_axis(x, axis: int, size: int):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, pad)


def to_program(w: dict, m: dict, cfg) -> dict:
    """The program's parameter tree (``repro.models.transformer``
    layout) holding the published weights ``w``."""
    from repro.models import transformer as T
    n = dims(m)
    kv, g = n["KV"], n["H"] // n["KV"]
    kvp, gp = cfg.padded_heads()
    vp = cfg.padded_vocab()
    if T.period_len(cfg) != 1:
        raise ValueError("only homogeneous decoder stacks are laid out")
    attn = {"wq": _pad_q(w["wq"], 2, kv, g, kvp, gp),
            "wk": _pad_axis(w["wk"], 2, kvp),
            "wv": _pad_axis(w["wv"], 2, kvp),
            "wo": _pad_q(w["wo"], 1, kv, g, kvp, gp)}
    if n["bias"]:
        attn["bq"] = _pad_q(w["bq"], 1, kv, g, kvp, gp)
        attn["bk"] = _pad_axis(w["bk"], 1, kvp)
        attn["bv"] = _pad_axis(w["bv"], 1, kvp)
    block = {"norm1": {"scale": w["norm1"]}, "attn": attn,
             "norm2": {"scale": w["norm2"]},
             "mlp": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                     "w_down": w["w_down"]}}
    params = {"embed": _pad_axis(w["embed"], 0, vp),
              "final_norm": {"scale": w["final_norm"]},
              "blocks": [block]}
    if not n["tied"]:
        params["lm_head"] = _pad_axis(w["lm_head"], 1, vp)
    return params


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


def make_program_params(seed: int, m: dict, cfg):
    """One jitted call: seed -> the program's parameter tree, on device."""
    key = seed_key(seed)
    shapes = jax.eval_shape(lambda k: to_program(published(k, m), m, cfg),
                            key)
    check_layout(shapes, cfg)
    params = jax.jit(lambda k: to_program(published(k, m), m, cfg))(key)
    return jax.block_until_ready(params)


def make_published(seed: int, m: dict):
    return jax.block_until_ready(
        jax.jit(lambda k: published(k, m))(seed_key(seed)))
