"""Pallas TPU kernels: tiled asymmetric quantize / dequantize, plus a
fused quantize-and-pack-int4 kernel.

TPU mapping: the tensor streams HBM -> VMEM in (block_m, block_n) tiles;
each tile is rounded onto the quantization grid on the VPU and written
back as uint8 codes — or, for the fused int4 kernel, code rows r and
r + M/2 are packed into one byte before the writeback, so the codes never
round-trip through HBM at int8 width. scale/mu ride either as (1, 1)
scalar blocks (per-tensor) or as (1, block_n) VMEM tiles indexed by the n
grid axis (per-output-column; DESIGN.md §4). Codes convert through int32
in both directions: Mosaic casts neither float -> uint8 nor uint8 ->
float directly. This is the execution form of paper Eq. 10 — the server
quantizes a model segment before "transmitting" it (on TPU: before
writing the compact weights to HBM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = (256, 512)


def _prep_scale_mu(scale, mu, n: int, bn: int, grid_rank: int = 2):
    """Normalize scale/mu to the (1, 1) per-tensor or (1, N) per-channel
    form and build the matching BlockSpec. Shared by every kernel in
    this package; grid axis 1 always walks the n tiles (``grid_rank`` is
    the kernel's grid arity — 2 for elementwise, 3 for matmul)."""
    scale = jnp.asarray(scale, jnp.float32)
    mu = jnp.asarray(mu, jnp.float32)
    per_channel = scale.size > 1 or mu.size > 1
    if per_channel:
        scale = jnp.broadcast_to(scale.reshape(-1), (n,)).reshape(1, n)
        mu = jnp.broadcast_to(mu.reshape(-1), (n,)).reshape(1, n)
        block = (1, bn)
        idx = (lambda i, j, kk: (0, j)) if grid_rank == 3 \
            else (lambda i, j: (0, j))
    else:
        scale = scale.reshape(1, 1)
        mu = mu.reshape(1, 1)
        block = (1, 1)
        idx = (lambda i, j, kk: (0, 0)) if grid_rank == 3 \
            else (lambda i, j: (0, 0))
    return scale, mu, pl.BlockSpec(block, idx)


def _codes(x_ref, scale_ref, mu_ref, levels: int):
    """Round a tile onto the grid -> int32 codes in [0, levels]."""
    x = x_ref[...].astype(jnp.float32)
    q = jnp.round((x - mu_ref[...]) / scale_ref[...])
    return jnp.clip(q, 0.0, float(levels)).astype(jnp.int32)


def _quantize_kernel(x_ref, scale_ref, mu_ref, o_ref, *, levels: int):
    o_ref[...] = _codes(x_ref, scale_ref, mu_ref, levels).astype(jnp.uint8)


def _dequantize_kernel(c_ref, scale_ref, mu_ref, o_ref, *, out_dtype):
    c = c_ref[...].astype(jnp.int32).astype(jnp.float32)
    o_ref[...] = (c * scale_ref[...] + mu_ref[...]).astype(out_dtype)


def _quantize_pack4_kernel(xlo_ref, xhi_ref, scale_ref, mu_ref, o_ref):
    # byte row r = code row r (low nibble) | code row r + M/2 (high)
    lo = _codes(xlo_ref, scale_ref, mu_ref, 15)
    hi = _codes(xhi_ref, scale_ref, mu_ref, 15)
    o_ref[...] = (lo | (hi << 4)).astype(jnp.uint8)


def quantize_pallas(x, scale, mu, bits: int, block=DEFAULT_BLOCK,
                    interpret: bool = False):
    """x (M, N) float -> uint8 codes. bits <= 8. scale/mu per-tensor or
    per-output-column (broadcastable to (1, N))."""
    assert bits <= 8
    m, n = x.shape
    bm, bn = min(block[0], m), min(block[1], n)
    assert m % bm == 0 and n % bn == 0, (x.shape, block)
    grid = (m // bm, n // bn)
    scale, mu, smspec = _prep_scale_mu(scale, mu, n, bn)
    return pl.pallas_call(
        functools.partial(_quantize_kernel, levels=(1 << bits) - 1),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            smspec,
            smspec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.uint8),
        interpret=interpret,
    )(x, scale, mu)


def dequantize_pallas(codes, scale, mu, out_dtype=jnp.bfloat16,
                      block=DEFAULT_BLOCK, interpret: bool = False):
    m, n = codes.shape
    bm, bn = min(block[0], m), min(block[1], n)
    assert m % bm == 0 and n % bn == 0
    grid = (m // bm, n // bn)
    scale, mu, smspec = _prep_scale_mu(scale, mu, n, bn)
    return pl.pallas_call(
        functools.partial(_dequantize_kernel, out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            smspec,
            smspec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(codes, scale, mu)


def quantize_pack4_pallas(x, scale, mu, block=DEFAULT_BLOCK,
                          interpret: bool = False):
    """Fused Eq. 10 + int4 wire packing: x (M, N) float -> (M//2, N)
    uint8, two 4-bit codes per byte (low nibble = row r, high nibble =
    row r + M/2 — the qmatmul4 layout). One VMEM pass; the codes never
    round-trip through HBM at int8 width. ``block[0]`` counts packed
    rows."""
    m, n = x.shape
    assert m % 2 == 0, "int4 packing pairs the two row halves"
    mh = m // 2
    bm, bn = min(block[0], mh), min(block[1], n)
    assert mh % bm == 0 and n % bn == 0, (x.shape, block)
    grid = (mh // bm, n // bn)
    half = mh // bm                       # row-block offset of the high half
    scale, mu, smspec = _prep_scale_mu(scale, mu, n, bn)
    return pl.pallas_call(
        _quantize_pack4_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i + half, j)),
            smspec,
            smspec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mh, n), jnp.uint8),
        interpret=interpret,
    )(x, x, scale, mu)
