"""Pallas TPU kernel: dequantize-fused matmul (W8A16 / W4A16).

The QPART-quantized weights stay packed in HBM (uint8 codes, or two 4-bit
codes per byte); each weight tile is dequantized in VMEM right before an
MXU dot with the activation tile, and partial products accumulate in a
VMEM f32 scratch across the k grid dimension. HBM traffic for weights is
b/16 of the bf16 baseline — the paper's payload saving (Eq. 14)
re-expressed for the TPU memory hierarchy (DESIGN.md §3).

Scale/zero granularity (DESIGN.md §4): ``scale``/``mu`` may be

  * scalars (any size-1 shape)  — per-tensor, rides as a (1, 1) block, or
  * per-output-column vectors   — any shape broadcastable to (1, N);
    streamed through VMEM as (1, block_n) tiles indexed by the n grid
    axis, so ``quantize_stacked``'s per-channel metadata (a period slice
    ``scale[i]`` of shape (1, N)) feeds the kernel without reformatting.

Int4 wire layout: the packed matrix is (K/2, N); byte row r holds code
row r in its low nibble and code row r + K/2 in its high nibble. A
packed tile therefore dequantizes into two (block_k, block_n) tiles
that multiply the two halves of the activation's contraction axis —
two MXU dots, no lane interleave (Mosaic refuses the nibble interleave
an adjacent-column layout needs).

Codes widen uint8 -> int32 -> float32 in-kernel: Mosaic has no direct
cast between uint8 and floating point.

Block sizes come from the caller (``ops.qdense`` picks them with
``ops._tile``): each of the last two block dims is a multiple of the
(8, 128) tiling or spans the whole array dim — what Mosaic accepts.
Defaults (256, 512, 256) keep the working set (x 256x512 bf16 + w
512x256 codes + acc 256x256 f32) ~ 0.6 MB, far under VMEM, so the
pipeline can run double-buffered.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quantize import _prep_scale_mu

BM, BK, BN = 256, 512, 256


def _dequant(codes, scale, mu):
    """Integer codes -> f32 weights. scale/mu blocks are (1, 1) or
    (1, bn) and broadcast over the tile."""
    return codes.astype(jnp.int32).astype(jnp.float32) * scale + mu


def _qmm_kernel(x_ref, w_ref, scale_ref, mu_ref, o_ref, acc_ref, *,
                n_k: int, out_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _dequant(w_ref[...], scale_ref[...], mu_ref[...])
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def qmatmul_pallas(x, w_codes, scale, mu, out_dtype=jnp.bfloat16,
                   bm=BM, bk=BK, bn=BN, interpret: bool = False):
    """x (M, K) bf16/f32 @ dequant(w_codes (K, N) uint8) -> (M, N).
    scale/mu: per-tensor scalars or per-output-column (1, N) / (N,)."""
    m, k = x.shape
    k2, n = w_codes.shape
    assert k == k2
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (x.shape, w_codes.shape)
    grid = (m // bm, n // bn, k // bk)
    scale, mu, smspec = _prep_scale_mu(scale, mu, n, bn, grid_rank=3)
    return pl.pallas_call(
        functools.partial(_qmm_kernel, n_k=k // bk, out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            smspec,
            smspec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w_codes, scale, mu)


def _qmm4_kernel(xlo_ref, xhi_ref, wp_ref, scale_ref, mu_ref, o_ref,
                 acc_ref, *, n_k: int, out_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    packed = wp_ref[...].astype(jnp.int32)
    scale, mu = scale_ref[...], mu_ref[...]
    w_lo = _dequant(packed & 0xF, scale, mu)       # code rows [0, K/2)
    w_hi = _dequant(packed >> 4, scale, mu)        # code rows [K/2, K)
    acc_ref[...] += (
        jnp.dot(xlo_ref[0].astype(jnp.float32), w_lo,
                preferred_element_type=jnp.float32)
        + jnp.dot(xhi_ref[0].astype(jnp.float32), w_hi,
                  preferred_element_type=jnp.float32))

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def qmatmul4_pallas(x, packed, scale, mu, out_dtype=jnp.bfloat16,
                    bm=BM, bk=BK // 2, bn=BN, interpret: bool = False):
    """x (M, K) @ dequant(packed (K//2, N) uint8) -> (M, N), two 4-bit
    codes per byte (low nibble = row r, high nibble = row r + K/2).
    ``bk`` counts PACKED rows. scale/mu: per-tensor scalars or
    per-output-column (1, N) / (N,)."""
    m, k = x.shape
    kh, n = packed.shape
    assert k == 2 * kh, (x.shape, packed.shape)
    bm, bk, bn = min(bm, m), min(bk, kh), min(bn, n)
    assert m % bm == 0 and kh % bk == 0 and n % bn == 0, \
        (x.shape, packed.shape, (bm, bk, bn))
    grid = (m // bm, n // bn, kh // bk)
    scale, mu, smspec = _prep_scale_mu(scale, mu, n, bn, grid_rank=3)
    # (2, M, K/2): the contraction halves the low / high nibbles multiply,
    # each a legal block operand whatever K/2's alignment
    xh = x.reshape(m, 2, kh).transpose(1, 0, 2)
    return pl.pallas_call(
        functools.partial(_qmm4_kernel, n_k=kh // bk, out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda i, j, kk: (0, i, kk)),
            pl.BlockSpec((1, bm, bk), lambda i, j, kk: (1, i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            smspec,
            smspec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xh, xh, packed, scale, mu)
