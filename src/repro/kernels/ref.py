"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize_ref(x, scale, mu, bits: int):
    """Asymmetric uniform quantization to int codes (int8 storage)."""
    levels = (1 << bits) - 1
    codes = jnp.clip(jnp.round((x.astype(jnp.float32) - mu) / scale), 0, levels)
    # unsigned storage: 8-bit codes span 0..255 and WRAP in int8
    return codes.astype(jnp.uint8 if bits <= 8 else jnp.int32)


def dequantize_ref(codes, scale, mu, dtype=jnp.bfloat16):
    return (codes.astype(jnp.float32) * scale + mu).astype(dtype)


def qmatmul_ref(x, w_codes, scale, mu, out_dtype=jnp.float32):
    """x (M,K) x dequant(w_codes (K,N)) -> (M,N)."""
    w = w_codes.astype(jnp.float32) * scale + mu
    return jnp.dot(x.astype(jnp.float32), w,
                   preferred_element_type=jnp.float32).astype(out_dtype)


def pack_int4_ref(codes, axis: int = 0):
    """int codes in [0,15] -> packed bytes, halving ``axis``: byte i
    holds code i (low nibble) and code i + n/2 (high nibble) along it.
    On a (K, N) weight (axis 0) this is the qmatmul4 wire layout."""
    lo, hi = jnp.split(codes.astype(jnp.uint8), 2, axis=axis)
    return lo | (hi << 4)


def unpack_int4_ref(packed, axis: int = 0):
    p = packed.astype(jnp.int32)
    return jnp.concatenate([p & 0xF, p >> 4], axis=axis)


def quantize_pack4_ref(x, scale, mu):
    """Oracle for the fused quantize-and-pack-int4 kernel."""
    return pack_int4_ref(quantize_ref(x, scale, mu, 4))


def qmatmul4_ref(x, packed, scale, mu, out_dtype=jnp.float32):
    """x (M,K) x dequant(packed (K/2,N), ``pack_int4_ref`` layout)."""
    codes = unpack_int4_ref(packed)
    w = codes.astype(jnp.float32) * scale + mu
    return jnp.dot(x.astype(jnp.float32), w,
                   preferred_element_type=jnp.float32).astype(out_dtype)


NEG_INF = -1e30


def decode_attention_ref(q, ck, cv, pos):
    """Single-token decode attention over a ring-buffer KV cache — the
    ``lax.scan``-path math of ``models.attention.attention_decode``,
    extracted verbatim (the Pallas decode kernel's allclose target).

    q (B, KVp, Gp, hd) the post-RoPE query of ONE token; ck/cv
    (B, buf, KVp, hd) the cache AFTER the current token's K/V were
    written at slot ``pos % buf`` (any storage dtype — bf16 / float8 for
    quantized device segments); ``pos`` the scalar absolute position.
    Returns (B, KVp, Gp, hd) in the query dtype.
    """
    hd = q.shape[-1]
    buf = ck.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    slot = pos % buf
    sc = jnp.einsum("bkgd,bskd->bkgs", q, ck.astype(q.dtype),
                    preferred_element_type=jnp.float32) * hd ** -0.5
    # validity: once the ring has wrapped (pos+1 >= buf) every slot is
    # live; before that only slots 0..slot have been written.
    idx = jnp.arange(buf)
    valid = (pos + 1 >= buf) | (idx <= slot)
    sc = jnp.where(valid[None, None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    # PV in the QUERY dtype: the cache may hold low-precision storage
    # dtypes that are fine as storage but catastrophic as accumulators
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(q.dtype),
                     cv.astype(q.dtype),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)
