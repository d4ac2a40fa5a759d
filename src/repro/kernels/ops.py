"""jit'd public wrappers for the Pallas kernels.

``kernel_mode`` picks the lane the model code runs: on a TPU backend the
kernels compile to Mosaic; on the CPU the default is the pure-jnp
``ref.py`` oracle, and ``REPRO_KERNELS=interpret`` runs the kernel
bodies in Python instead (how the test-suite validates them against
``ref.py``). The older ``use_pallas`` wrappers below run the kernels in
interpret mode off the TPU, and ``use_pallas=False`` falls back to the
oracle — the mode the dry-run uses so the lowered HLO stays portable.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.qmatmul import BK, BM, BN, qmatmul4_pallas, qmatmul_pallas
from repro.kernels.quantize import (dequantize_pallas, quantize_pack4_pallas,
                                    quantize_pallas)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Execution-mode dispatch (PR 9). models/ call these entry points instead of
# branching on the backend themselves; one env var picks the lane for the
# whole decode path.

KERNEL_MODES = ("auto", "kernel", "interpret", "reference")


def kernel_mode() -> str:
    """Resolve ``REPRO_KERNELS`` to the lane model code should execute.

    ``auto`` (default) -> compiled Pallas on TPU, pure-jnp ``ref``/scan
    path on CPU — the CPU default stays bit-for-bit the pre-kernel
    behavior. ``kernel`` forces compiled Pallas, ``interpret`` runs the
    kernel bodies in Python (the CI correctness lane), ``reference``
    forces the jnp oracles everywhere.
    """
    mode = os.environ.get("REPRO_KERNELS", "").strip().lower() or "auto"
    if mode not in KERNEL_MODES:
        raise ValueError(
            f"REPRO_KERNELS={mode!r}: expected one of {KERNEL_MODES}")
    if mode == "auto":
        return "kernel" if _on_tpu() else "reference"
    return mode


def decode_attention(q, ck, cv, pos):
    """Single-token decode attention over a ring-buffer cache, dispatched
    by :func:`kernel_mode`. q (B, KVp, Gp, hd); ck/cv (B, buf, KVp, hd)
    post-write; pos scalar absolute position -> (B, KVp, Gp, hd)."""
    mode = kernel_mode()
    if mode == "reference":
        return ref.decode_attention_ref(q, ck, cv, pos)
    return decode_attention_pallas(q, ck, cv, pos,
                                   interpret=mode == "interpret")


def _tile(dim: int, pref: int, align: int = 128) -> int:
    """Block size for one matmul dim: the whole dim when it fits under
    ``pref``, else the largest multiple of ``align`` <= pref dividing it,
    else the whole dim. Mosaic takes a block dim only when it is a
    multiple of the (8, 128) tiling or spans the array dim — model dims
    are not always multiples of the defaults (d_model 576 has no
    128-multiple divisor, so it runs as one block)."""
    if dim <= pref:
        return dim
    for t in range(pref - pref % align, 0, -align):
        if dim % t == 0:
            return t
    return dim


def is_wire_struct(w) -> bool:
    """True for a quantized wire struct ({codes|codes_packed, scale, mu})."""
    return isinstance(w, dict) and ("codes" in w or "codes_packed" in w)


def qdense(x, w, n_contract: int = 1, out_dtype=None):
    """Quantized dense contraction: trailing axes of ``x`` against the
    ``n_contract`` leading axes of wire-struct ``w``, through the
    dequantize-fused qmatmul/qmatmul4 kernels (by :func:`kernel_mode`).

    ``w`` is {codes (K..., N...) uint8 | codes_packed (K0/2, K1..., N...),
    scale, mu} with per-tensor (size-1) or per-output-column metadata;
    int4 codes pack the two halves of the first contraction axis
    (``ref.pack_int4_ref``), which are the two halves of the flattened
    contraction. The trailing axes of ``x`` whose product equals
    prod(K...) are the contraction; output is x-batch-axes + (N...) in
    ``out_dtype`` (default ``x.dtype``).
    """
    out_dtype = out_dtype or x.dtype
    packed = "codes_packed" in w
    codes = w["codes_packed"] if packed else w["codes"]
    k = math.prod(codes.shape[:n_contract]) * (2 if packed else 1)
    out_tail = list(codes.shape[n_contract:])
    # peel trailing x axes until they cover the contraction size
    i, tail = x.ndim, 1
    while tail < k:
        i -= 1
        tail *= x.shape[i]
    assert tail == k, (x.shape, codes.shape, n_contract)
    batch = x.shape[:i]
    x2 = x.reshape(-1, k)
    n = math.prod(out_tail)
    codes2 = codes.reshape(-1, n)                # (K, N) or packed (K/2, N)

    def _meta2d(v):
        """scale/mu -> the (1, 1) / (1, N) layout qmatmul expects. The
        quantize_stacked metadata keeps size-1 contraction axes and
        broadcasts over the flattened output columns (e.g. per-head-dim
        scale for a (D, H, hd) weight)."""
        if v.size == 1:
            return v.reshape(1, 1)
        v = v[(0,) * n_contract]               # drop contraction axes
        return jnp.broadcast_to(v, tuple(out_tail)).reshape(1, n)

    scale, mu = _meta2d(w["scale"]), _meta2d(w["mu"])

    mode = kernel_mode()
    if mode == "reference":
        out = (ref.qmatmul4_ref(x2, codes2, scale, mu, out_dtype) if packed
               else ref.qmatmul_ref(x2, codes2, scale, mu, out_dtype))
    else:
        bm = _tile(x2.shape[0], BM, align=8)
        bk = _tile(codes2.shape[0], BK // 2 if packed else BK)
        fn = qmatmul4_pallas if packed else qmatmul_pallas
        out = fn(x2, codes2, scale, mu, out_dtype, bm=bm, bk=bk,
                 bn=_tile(n, BN), interpret=mode == "interpret")
    return out.reshape(batch + tuple(out_tail))


@functools.partial(jax.jit, static_argnames=("bits", "use_pallas"))
def quantize_tensor(x, scale, mu, bits: int = 8, use_pallas: bool = True):
    if use_pallas and x.ndim == 2:
        return quantize_pallas(x, scale, mu, bits, interpret=not _on_tpu())
    return ref.quantize_ref(x, scale, mu, bits)


@functools.partial(jax.jit, static_argnames=("out_dtype", "use_pallas"))
def dequantize_tensor(codes, scale, mu, out_dtype=jnp.bfloat16,
                      use_pallas: bool = True):
    if use_pallas and codes.ndim == 2:
        return dequantize_pallas(codes, scale, mu, out_dtype,
                                 interpret=not _on_tpu())
    return ref.dequantize_ref(codes, scale, mu, out_dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "use_pallas"))
def qmatmul(x, w_codes, scale, mu, out_dtype=jnp.bfloat16,
            use_pallas: bool = True):
    """Quantized matmul: x @ dequant(w_codes)."""
    if use_pallas:
        return qmatmul_pallas(x, w_codes, scale, mu, out_dtype,
                              interpret=not _on_tpu())
    return ref.qmatmul_ref(x, w_codes, scale, mu, out_dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "use_pallas"))
def qmatmul4(x, packed, scale, mu, out_dtype=jnp.bfloat16,
             use_pallas: bool = True):
    """int4-packed quantized matmul."""
    if use_pallas:
        return qmatmul4_pallas(x, packed, scale, mu, out_dtype,
                               interpret=not _on_tpu())
    return ref.qmatmul4_ref(x, packed, scale, mu, out_dtype)


def pack_int4(codes):
    return ref.pack_int4_ref(codes)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def quantize_pack4(x, scale, mu, use_pallas: bool = True):
    """Fused quantize + int4 wire packing: (M, N) float -> (M, N//2)
    uint8, two codes per byte. scale/mu per-tensor or per-column."""
    if use_pallas and x.ndim == 2:
        return quantize_pack4_pallas(x, scale, mu, interpret=not _on_tpu())
    return ref.quantize_pack4_ref(x, scale, mu)
