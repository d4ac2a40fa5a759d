"""Uniform asymmetric quantizer (paper Eq. 9–10).

Given a tensor c and bit-width b the quantization set is the uniform grid
``Q = [mu : (phi-mu)/(2^b - 1) : phi]`` and ``Q(c) = argmin_{q in Q} |c-q|``
— i.e. round-to-nearest onto the grid. We expose:

  * ``quantize`` / ``dequantize``  — integer codes + (scale, zero) metadata,
  * ``fake_quant``                 — quantize-dequantize in one pass (what
                                      the accuracy/noise calibration uses),
  * ``payload_bits``               — exact wire size of a quantized tensor.

The optimizer's closed-form bit-widths are continuous; deployment rounds
them with ``round_bits`` (ceil preserves the accuracy constraint since
noise is monotonically decreasing in b).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def qrange(x):
    """Tensor range (mu, phi) used by the asymmetric quantizer."""
    return jnp.min(x), jnp.max(x)


def quantize(x, bits: int, mu=None, phi=None):
    """-> (codes int32, scale, mu). codes in [0, 2^bits - 1]. Either end
    of the grid may be pinned by the caller; the other defaults to the
    tensor's own range."""
    if mu is None:
        mu = jnp.min(x)
    if phi is None:
        phi = jnp.max(x)
    levels = (1 << int(bits)) - 1
    scale = jnp.maximum((phi - mu) / levels, 1e-12)
    codes = jnp.clip(jnp.round((x - mu) / scale), 0, levels).astype(jnp.int32)
    return codes, scale, mu


def dequantize(codes, scale, mu, dtype=jnp.float32):
    return (codes.astype(jnp.float32) * scale + mu).astype(dtype)


def fake_quant(x, bits: int):
    """Quantize-dequantize; identity gradient (STE) for completeness."""
    codes, scale, mu = quantize(x, bits)
    return dequantize(codes, scale, mu, x.dtype)


def quant_noise_energy(x, bits: int) -> jnp.ndarray:
    """Measured ``||x - Q(x)||_2^2`` — the empirical LHS of Eq. 18/19."""
    err = x - fake_quant(x, bits)
    return jnp.sum(jnp.square(err.astype(jnp.float32)))


def analytic_noise_scale(x) -> jnp.ndarray:
    """Analytic s such that ||sigma(b)||^2 ~= s * e^(-ln4 * b).

    Uniform round-off noise has variance step^2/12 with
    step = range/(2^b - 1) ~= range * 2^-b, so the energy over n elements is
    ``n * range^2 / 12 * 4^-b`` — i.e. the paper's exponential law with
    s = n * range^2 / 12. Tests check the empirical fit matches.
    """
    mu, phi = qrange(x)
    n = x.size
    return n * jnp.square(phi - mu) / 12.0


def round_bits(b, lo: int = 2, hi: int = 16) -> np.ndarray:
    """Continuous solver output -> deployable integer bit-widths. Rounded
    on the host (no device dispatch), in the float width ``jnp`` computes
    in, so a value a hair above an integer rounds as it would there."""
    b = np.asarray(b, jax.dtypes.canonicalize_dtype(np.float64))
    return np.clip(np.ceil(b), lo, hi).astype(np.int32)


def payload_bits(num_elements: int, bits) -> jnp.ndarray:
    """Wire size in bits: Eq. 14 term ``b * z`` (+ f32 scale/zero header)."""
    return num_elements * bits + 2 * 32


def quantize_stacked(leaf, bits: int = 8, per_channel: bool = True,
                     use_pallas=None):
    """Real int8/int4-code quantization of a stacked (num_periods, ...)
    weight. Granularity: per-period AND (by default) per-output-column —
    scale/mu keep the leading period axis and the trailing channel axis,
    e.g. (P, 1, N) for a (P, K, N) leaf. Returns the wire representation
    ``{"codes", "scale", "mu"}`` the serving path stores in HBM and
    dequantizes at block entry (transformer._dequant_block); a period
    slice (``codes[i]``, ``scale[i]``, ``mu[i]``) feeds the per-channel
    Pallas qmatmul kernels directly (DESIGN.md §4).

    Metadata footprint: per-channel carries 2·32·N header bits per
    period vs the per-tensor 64 — a 64/(K·b) relative overhead (~3% for
    a 512-row int4 layer, ~0.4% int8). ``payload_bits`` and the
    planner's Eq. 14 accounting model the per-tensor header; pass
    ``per_channel=False`` where exact wire-size accounting outweighs
    the accuracy gain.

    bits <= 4 packs two codes per byte along the first axis of each
    period slice (the qmatmul4 kernel's wire layout: byte row r = code
    row r in the low nibble, row r + K/2 in the high one) — the HBM
    weight footprint really halves vs int8. On TPU the quantize and the
    pack run as ONE fused Pallas pass per period
    (kernels.quantize_pack4_pallas) instead of materializing int8 codes
    and slicing them; ``use_pallas`` requests the path (None = auto: TPU
    backend only) but leaves whose rows/columns don't tile the kernel
    blocks fall back to the jnp pack — same bytes, just not fused."""
    if per_channel and leaf.ndim >= 3:
        axes = tuple(range(1, leaf.ndim - 1))     # keep periods + channels
    else:
        axes = tuple(range(1, leaf.ndim))
    mu = jnp.min(leaf, axis=axes, keepdims=True)
    phi = jnp.max(leaf, axis=axes, keepdims=True)
    levels = (1 << int(bits)) - 1
    scale = jnp.maximum((phi - mu) / levels, 1e-12)
    meta = {"scale": scale.astype(jnp.float32),
            "mu": mu.astype(jnp.float32)}
    if bits <= 4 and leaf.ndim >= 2 and leaf.shape[1] % 2 == 0:
        # key name encodes the packing (static pytree structure, so the
        # dequant site can branch without tracing a flag)
        return {"codes_packed": _pack4(leaf, meta["scale"], meta["mu"],
                                       use_pallas), **meta}
    codes = jnp.clip(jnp.round((leaf - mu) / scale), 0, levels)
    return {"codes": codes.astype(jnp.uint8), **meta}


def _pack4(leaf, scale, mu, use_pallas):
    """Quantize to 4-bit codes and pack the row halves of each period
    slice. Routes the (rows, columns) period slices through the fused
    Pallas kernel when possible; otherwise the jnp pack (also the
    interpret-mode oracle)."""
    from repro.kernels import ops, ref  # late import: kernels pull in pallas
    from repro.kernels.quantize import DEFAULT_BLOCK

    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    rows, cols = leaf.shape[1], math.prod(leaf.shape[2:])
    bm, bn = DEFAULT_BLOCK       # mirror quantize_pack4_pallas's asserts
    tileable = leaf.ndim >= 3 and (rows // 2) % min(bm, rows // 2) == 0 \
        and cols % min(bn, cols) == 0
    if use_pallas and tileable:
        flat = leaf.reshape(leaf.shape[0], rows, cols)
        s2, m2 = (jnp.broadcast_to(v, (leaf.shape[0], 1) + leaf.shape[2:])
                  .reshape(-1, 1, cols) for v in (scale, mu))
        # one batched dispatch over the period axis, not P kernel launches
        packed = jax.vmap(ops.quantize_pack4)(flat, s2, m2)
        return packed.reshape((leaf.shape[0], rows // 2) + leaf.shape[2:])
    codes = jnp.clip(jnp.round((leaf - mu) / scale), 0, 15)
    return ref.pack_int4_ref(codes, axis=1)


def stacked_wire_bits(q) -> int:
    """EXACT wire/HBM size in bits of a ``quantize_stacked`` struct —
    codes plus the real scale/zero metadata (which, per-channel, is
    2·32·N per period rather than the 64-bit header ``payload_bits``
    models). Use this when accounting for what serving actually ships."""
    codes = q["codes_packed"] if "codes_packed" in q else q["codes"]
    return int(codes.size) * 8 + 32 * (int(q["scale"].size)
                                       + int(q["mu"].size))


QUANTIZABLE = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
               "w_z", "w_x", "w_out", "w_B", "w_C", "w_dt")


def quantize_params_for_serving(params, bits: int = 8,
                                per_channel: bool = True):
    """Quantize every big block weight of a transformer param tree (the
    QPART device-segment quantization applied to the whole serving stack:
    weights live int8 in HBM, cutting the decode memory-roofline term).
    ``per_channel`` follows quantize_stacked: better accuracy for a
    2·32·N-bit-per-period metadata footprint (see its docstring)."""
    def walk(node, under_blocks=False):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if under_blocks and k in QUANTIZABLE and hasattr(v, "ndim") \
                        and v.ndim >= 3:
                    out[k] = quantize_stacked(v, bits, per_channel=per_channel)
                else:
                    out[k] = walk(v, under_blocks)
            return out
        if isinstance(node, list):
            return [walk(v, True) for v in node]
        return node

    return {k: ([walk(b, True) for b in v] if k == "blocks" else v)
            for k, v in params.items()}


def quantize_tree(params, bits_per_leaf):
    """Fake-quantize a parameter tree with per-leaf bit-widths (int or map
    keyed like the tree). Used to materialize the model segment QPART ships
    to the device."""
    leaves, treedef = jax.tree.flatten(params)
    if isinstance(bits_per_leaf, int):
        bits_list = [bits_per_leaf] * len(leaves)
    else:
        bits_list = jax.tree.flatten(bits_per_leaf)[0]
    out = [fake_quant(x, int(b)) for x, b in zip(leaves, bits_list)]
    return jax.tree.unflatten(treedef, out)
