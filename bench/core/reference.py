"""Plain reference of the served function, and its lower-precision control.

What a deployed plan (cut ``p``, per-layer weight bits ``bits_w``, hop
bits ``bits_x``) computes, written out in float32 ``jax.numpy`` at
``highest`` matmul precision, one layer at a time, from weights that
``weights.published`` regenerates from the seed. It imports nothing of
the program and takes nothing the program made; of the plan it takes the
deployed cut and bit-widths, which define the function requested.

Per layer l of a decoder with RMSNorm, RoPE (rotate-half), grouped-query
causal attention and a SwiGLU MLP, at the published head counts:

* l < p (device segment): every weight tensor of the layer (projections,
  biases, norm scales) fake-quantized at ``bits_w[l]`` on a per-tensor
  asymmetric grid: levels 2^b - 1, scale (max - min) / levels, codes
  round((w - min) / scale) clipped to the levels (paper Eq. 9-10). Its
  keys and values are read back through the device cache's storage type,
  float8 e4m3 where ``bits_x <= 8``.
* after layer p - 1 (the hop): the hidden state quantized at ``bits_x``
  on one grid per position (min and max over the hidden dimension).
* l >= p (server tail): full precision.

Then the final norm and the unembedding (the tied embedding where the
model ties it). Departures from the published model: none in the
mathematics; rms_norm_eps and rope_theta are the published ones.

The control (``compute="fp8"``) is the same function with every matmul's
two operands cast to float8 e4m3 (accumulated in float32): the precision
below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.core.weights import dims, make_published

F8 = jnp.float8_e4m3fn
LAYER_KEYS = ("norm1", "wq", "wk", "wv", "wo", "bq", "bk", "bv", "norm2",
              "w_gate", "w_up", "w_down")


def fake_quant(w, bits, axis=None):
    """Asymmetric round-to-nearest grid over ``axis`` (None: the whole
    tensor) at ``bits`` (a traced scalar is fine)."""
    levels = 2.0 ** bits - 1.0
    mu = jnp.min(w, axis=axis, keepdims=axis is not None)
    phi = jnp.max(w, axis=axis, keepdims=axis is not None)
    scale = jnp.maximum((phi - mu) / levels, 1e-12)
    return jnp.clip(jnp.round((w - mu) / scale), 0.0, levels) * scale + mu


def _mm(spec, a, b, compute):
    if compute == "fp8":
        return jnp.einsum(spec, a.astype(F8), b.astype(F8),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, pos, theta):
    """x (S, heads, hd); rotate-half RoPE at absolute positions ``pos``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq          # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(x, lw, dev, bits, kv8, *, n, compute):
    """One decoder layer over the whole (padded) sequence x (S, D)."""
    lw = {k: jnp.where(dev, fake_quant(v, bits), v) for k, v in lw.items()}
    s = x.shape[0]
    pos = jnp.arange(s)
    kv, g, hd = n["KV"], n["H"] // n["KV"], n["hd"]
    h = _rmsnorm(x, lw["norm1"], n["eps"])
    q = _mm("sd,dhk->shk", h, lw["wq"], compute)
    k = _mm("sd,dhk->shk", h, lw["wk"], compute)
    v = _mm("sd,dhk->shk", h, lw["wv"], compute)
    if n["bias"]:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    q = _rope(q, pos, n["theta"])
    k = _rope(k, pos, n["theta"])
    cache8 = dev & kv8
    k = jnp.where(cache8, k.astype(F8).astype(jnp.float32), k)
    v = jnp.where(cache8, v.astype(F8).astype(jnp.float32), v)
    q = q.reshape(s, kv, g, hd)
    sc = _mm("qkgd,tkd->kgqt", q, k, compute) * hd ** -0.5
    causal = pos[:, None] >= pos[None, :]
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = _mm("kgqt,tkd->qkgd", p, v, compute).reshape(s, kv * g, hd)
    x = x + _mm("shk,hkd->sd", o, lw["wo"], compute)
    h2 = _rmsnorm(x, lw["norm2"], n["eps"])
    a = _mm("sd,df->sf", h2, lw["w_gate"], compute)
    u = _mm("sd,df->sf", h2, lw["w_up"], compute)
    return x + _mm("sf,fd->sd", jax.nn.silu(a) * u, lw["w_down"], compute)


def _head(x, rows, final_norm, head, *, n, compute):
    h = _rmsnorm(x[rows], final_norm, n["eps"])
    return _mm("sd,dv->sv", h, head, compute)


class Reference:
    """The reference for one model and seed. ``logits`` runs the
    function of a plan over one token sequence and returns the logits at
    the rows asked for."""

    def __init__(self, model: dict, seed: int, seq_pad: int, rows_pad: int):
        self.n = dims(model)
        self.w = make_published(seed, model)
        self.seq_pad, self.rows_pad = seq_pad, rows_pad
        self.head = self.w["embed"].T if self.n["tied"] else self.w["lm_head"]
        self._layer = {c: jax.jit(functools.partial(_layer, n=self.n,
                                                    compute=c))
                       for c in ("f32", "fp8")}
        self._head = {c: jax.jit(functools.partial(_head, n=self.n,
                                                   compute=c))
                      for c in ("f32", "fp8")}
        self._hop = jax.jit(lambda x, b: fake_quant(x, b, axis=-1))

    def logits(self, tokens: np.ndarray, rows: np.ndarray, p: int,
               bits_w, bits_x: int, compute: str = "f32") -> np.ndarray:
        """tokens (S,) ids; rows: the positions whose next-token logits
        are wanted -> (len(rows), V) float32."""
        s = len(tokens)
        if s > self.seq_pad or len(rows) > self.rows_pad:
            raise ValueError(f"sequence {s} / rows {len(rows)} exceed the "
                             f"reference's padding")
        tok = np.zeros(self.seq_pad, np.int32)
        tok[:s] = tokens
        r = np.zeros(self.rows_pad, np.int32)
        r[:len(rows)] = rows
        kv8 = jnp.asarray(0 < bits_x <= 8)
        with jax.default_matmul_precision("highest"):
            x = self.w["embed"][jnp.asarray(tok)]
            for layer in range(self.n["L"]):
                dev = layer < p
                lw = {k: self.w[k][layer] for k in LAYER_KEYS if k in self.w}
                bits = float(bits_w[layer]) if dev else 16.0
                x = self._layer[compute](x, lw, jnp.asarray(dev),
                                         jnp.float32(bits), kv8)
                if dev and layer == p - 1:
                    x = self._hop(x, jnp.float32(bits_x))
            out = self._head[compute](x, jnp.asarray(r),
                                      self.w["final_norm"], self.head)
        return np.asarray(out, np.float32)[:len(rows)]
