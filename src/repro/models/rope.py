"""Rotary position embedding variants.

- ``rope``   : standard NTK-free llama RoPE over the full head dim.
- ``rope2d`` : GLM-style partial rotary — only the first half of the head
               dims rotate, the second half is passthrough.
- ``rope_interleaved`` : DeepSeek-V2/V3 RoPE — the pairs (2i, 2i+1) of
               the head dim rotate by pos * theta^(-2i/D) (llama's
               rotate-half pairs i with i + D/2 instead).
- ``mrope``  : Qwen2-VL multimodal RoPE — the head dim is split into three
               sections (t, h, w) each rotated by its own position stream.
               For pure-text tokens all three streams carry the same
               positions, which makes mrope degenerate to rope (this is the
               property Qwen2-VL relies on and that our tests check).

All functions take ``positions`` of shape (B, S) (int32) except mrope which
accepts (3, B, S); text callers pass the broadcasted triple.
"""
from __future__ import annotations

import jax.numpy as jnp

# M-RoPE section split of (head_dim // 2) angle slots, as fractions.
MROPE_SECTIONS = (1 / 4, 3 / 8, 3 / 8)   # t, h, w


def _angles(positions, dim: int, theta: float):
    """positions (..., S) -> angles (..., S, dim//2)."""
    half = dim // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    return positions[..., None].astype(jnp.float32) * freq


def _rotate(x, ang):
    """x (..., S, *head_dims, D), ang (..., S, D//2): rotate (even, odd)
    pairs, broadcasting over however many head dims sit between S and D
    (grouped GQA layout uses two: KV and G)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    while cos.ndim < x1.ndim:                 # insert head axes before D//2
        cos = cos[..., None, :]
        sin = sin[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(dt)


def _rotate_pairs(x, ang):
    """``_rotate`` on interleaved pairs: (x[2i], x[2i+1]) by ang[i]."""
    dt = x.dtype
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    while cos.ndim < x1.ndim:
        cos = cos[..., None, :]
        sin = sin[..., None, :]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(dt)


def apply_rope(kind: str, x, positions, theta: float):
    """x: (B, S, H, D); positions: (B, S) or (3, B, S) for mrope."""
    d = x.shape[-1]
    if kind == "none":
        return x
    if kind == "rope":
        return _rotate(x, _angles(positions, d, theta))
    if kind == "rope_interleaved":
        return _rotate_pairs(x, _angles(positions, d, theta))
    if kind == "rope2d":
        half = d // 2
        rot, keep = x[..., :half], x[..., half:]
        rot = _rotate(rot, _angles(positions, half, theta))
        return jnp.concatenate([rot, keep], axis=-1)
    if kind == "mrope":
        if positions.ndim == 2:       # text-only caller: broadcast
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        half = d // 2
        sizes = [int(round(f * half)) for f in MROPE_SECTIONS]
        sizes[-1] = half - sizes[0] - sizes[1]
        # Build per-slot positions by section, then a single rotate.
        pos_t, pos_h, pos_w = positions[0], positions[1], positions[2]
        seg = jnp.concatenate([
            jnp.broadcast_to(pos_t[..., None], pos_t.shape + (sizes[0],)),
            jnp.broadcast_to(pos_h[..., None], pos_h.shape + (sizes[1],)),
            jnp.broadcast_to(pos_w[..., None], pos_w.shape + (sizes[2],)),
        ], axis=-1)                   # (B, S, half)
        freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        ang = seg.astype(jnp.float32) * freq
        return _rotate(x, ang)
    raise ValueError(f"unknown rope kind {kind!r}")


def text_positions(batch: int, seq: int, offset=0):
    pos = jnp.arange(seq, dtype=jnp.int32)[None, :] + offset
    return jnp.broadcast_to(pos, (batch, seq))
