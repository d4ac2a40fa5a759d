"""Pieces every family's plain reference is written from
(``bench/families/<family>.py`` holds the layers and the ``Reference``):
float32 ``jax.numpy`` matmuls at ``highest`` precision or, for the
control, with both operands in float8 e4m3 (``compute="fp8"``, the
precision below the configurations' bfloat16), RMSNorm, rotate-half
RoPE, and the fake quantization that stands for a deployed plan's bits.

``fake_quant``: an asymmetric grid, levels 2^b - 1, scale (max - min) /
levels, codes round((w - min) / scale) clipped to the levels (paper Eq.
9-10); per tensor, or per row over one axis (the hop's hidden state).
These import nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn


def fake_quant(w, bits, axis=None):
    """Asymmetric round-to-nearest grid over ``axis`` (None: the whole
    tensor) at ``bits`` (a traced scalar is fine)."""
    levels = 2.0 ** bits - 1.0
    mu = jnp.min(w, axis=axis, keepdims=axis is not None)
    phi = jnp.max(w, axis=axis, keepdims=axis is not None)
    scale = jnp.maximum((phi - mu) / levels, 1e-12)
    return jnp.clip(jnp.round((w - mu) / scale), 0.0, levels) * scale + mu


def mm(spec, a, b, compute):
    if compute == "fp8":
        return jnp.einsum(spec, a.astype(F8), b.astype(F8),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def rope(x, pos, theta):
    """x (S, heads, hd); rotate-half RoPE at absolute positions ``pos``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq          # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
