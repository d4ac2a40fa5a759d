"""Pallas TPU kernel: grouped dequantize-fused matmul over the experts a
chip holds (the routed-expert layer's W8A16 / W4A16 matmuls).

Rows arrive grouped by expert: the dispatch (``models.moe``) sorts the
token-expert pairs that landed on held experts by expert and pads each
group to a whole number of ``bm``-row tiles, so every row tile belongs
to one expert. ``tile_expert[g]`` (scalar-prefetched) names the expert
of tile g, and ``n_tiles`` how many tiles hold rows. The weight block of
tile g is read from that expert's codes, dequantized in VMEM and fed to
the MXU, as in ``qmatmul`` (kernels/qmatmul.py). An expert that got no
row owns no tile, so none of its weight tiles is read.

The grid is static (g over an upper bound of tiles, n tiles, k tiles).
Grid steps past ``n_tiles`` compute nothing, and their block indices
repeat the last live step's, so the pipeline issues no copy for them:
their weight, activation and output blocks are the ones already in
VMEM. Their output rows are left unwritten; the dispatch reads only the
rows of live tiles.

Scale/zero: one (scale, mu) per tensor, shared by every expert of the
stack (the grid ``_build_qstacked`` and ``split_blocks`` put on a layer's
expert stack). Int4 codes pack the row halves of each expert's
contraction axis (byte row r = code row r in the low nibble, row r + K/2
in the high one), the ``qmatmul4`` layout, so a packed tile dequantizes
into two tiles that multiply the two halves of the activation's
contraction axis.

Activations and the output are float32 (``bm`` = 8 rows is a legal f32
tile; a decode step's tiles hold one row each). The kernel is named
``moe_gmm`` in its ``kernel_metadata``, so a trace tells its calls from
the dense ``qmatmul`` calls, which carry no such name.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "moe_gmm"


def _live_block(g, nt):
    """(live, last live tile) for grid step g given n_tiles ref."""
    n = nt[0]
    return g < n, jnp.maximum(n - 1, 0)


def _gmm_kernel(te_ref, nt_ref, *refs, n_k: int, packed: bool):
    if packed:
        xlo_ref, xhi_ref, w_ref, scale_ref, mu_ref, o_ref, acc_ref = refs
    else:
        x_ref, w_ref, scale_ref, mu_ref, o_ref, acc_ref = refs
    g, kk = pl.program_id(0), pl.program_id(2)

    @pl.when(g < nt_ref[0])
    def _live():
        @pl.when(kk == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        scale, mu = scale_ref[...], mu_ref[...]
        codes = w_ref[0].astype(jnp.int32)
        if packed:
            w_lo = (codes & 0xF).astype(jnp.float32) * scale + mu
            w_hi = (codes >> 4).astype(jnp.float32) * scale + mu
            acc_ref[...] += (
                jnp.dot(xlo_ref[0], w_lo, preferred_element_type=jnp.float32)
                + jnp.dot(xhi_ref[0], w_hi,
                          preferred_element_type=jnp.float32))
        else:
            w = codes.astype(jnp.float32) * scale + mu
            acc_ref[...] += jnp.dot(x_ref[...], w,
                                    preferred_element_type=jnp.float32)

        @pl.when(kk == n_k - 1)
        def _flush():
            o_ref[...] = acc_ref[...]


def gmm_pallas(x, codes, scale, mu, tile_expert, n_tiles, *, bm: int,
               packed: bool, bk: int, bn: int, interpret: bool = False):
    """x (G*bm, K) f32 grouped rows @ dequant(codes[tile_expert[g]]) per
    row tile -> (G*bm, N) f32. codes (E, K, N) uint8, or (E, K/2, N)
    packed int4 (``bk`` then counts packed rows); scale/mu per-tensor
    (size 1); tile_expert (G,) int32; n_tiles (1,) int32 live tiles
    (rows of later tiles are not written). Block sizes come from the
    caller (``ops.grouped_qdense``), as for ``qmatmul``."""
    m, k = x.shape
    e, kc, n = codes.shape
    assert k == (2 * kc if packed else kc), (x.shape, codes.shape)
    assert m % bm == 0 and kc % bk == 0 and n % bn == 0, \
        (x.shape, codes.shape, (bm, bk, bn))
    n_g, n_j, n_k = m // bm, n // bn, kc // bk
    scale = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    mu = jnp.asarray(mu, jnp.float32).reshape(1, 1)

    def rows(g, kk, nt):
        live, last = _live_block(g, nt)
        return jnp.where(live, g, last), jnp.where(live, kk, n_k - 1)

    def w_map(g, j, kk, te, nt):
        live, last = _live_block(g, nt)
        return (te[jnp.where(live, g, last)], jnp.where(live, kk, n_k - 1),
                jnp.where(live, j, n_j - 1))

    def o_map(g, j, kk, te, nt):
        live, last = _live_block(g, nt)
        return jnp.where(live, g, last), jnp.where(live, j, n_j - 1)

    meta = pl.BlockSpec((1, 1), lambda g, j, kk, te, nt: (0, 0))
    w_spec = pl.BlockSpec((1, bk, bn), w_map)
    if packed:
        xh = x.reshape(m, 2, kc).transpose(1, 0, 2)
        x_specs = [
            pl.BlockSpec((1, bm, bk),
                         lambda g, j, kk, te, nt: (0,) + rows(g, kk, nt)),
            pl.BlockSpec((1, bm, bk),
                         lambda g, j, kk, te, nt: (1,) + rows(g, kk, nt))]
        x_args = (xh, xh)
    else:
        x_specs = [pl.BlockSpec((bm, bk),
                                lambda g, j, kk, te, nt: rows(g, kk, nt))]
        x_args = (x,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n_g, n_j, n_k),
        in_specs=x_specs + [w_spec, meta, meta],
        out_specs=pl.BlockSpec((bm, bn), o_map),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_gmm_kernel, n_k=n_k, packed=packed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        name=KERNEL_NAME, metadata={"kernel": KERNEL_NAME},
        interpret=interpret,
    )(tile_expert.astype(jnp.int32), n_tiles.astype(jnp.int32).reshape(1),
      *x_args, codes, scale, mu)
