"""The dense family: a decoder with RMSNorm, RoPE (rotate-half),
grouped-query causal attention (optional QKV biases) and a SwiGLU MLP in
every layer. ``bench/configs/<config>.json`` without a ``"family"`` key
is of this family.

What a family file provides (``bench/core/spec.py`` loads it by path):
the published sizes (``dims``), the program's ``ModelConfig`` overrides
(``program_fields``), the weights drawn from the seed (``published``)
and their layout in the program's tree (``to_program``), the plain
reference (``Reference``), the CPU rehearsal's stand-in sizes
(``rehearsal``), how a device segment carries its weights
(``weights_kind``), and the work counts the share metrics divide by
(``decode_flops``, ``routed_matmuls``, ``attention_layers``).

Weights. ``published`` draws the weights at the published shapes (heads
as published, no padding) in one jitted call on the device.
``to_program`` lays the same arrays out as the program's parameter tree:
stacked over layers, query/key/value heads placed into the program's
padded head grid (``ModelConfig.padded_heads``) with zeros in the
padding. ``Reference`` regenerates the published arrays from the same
seed, so it shares no array with the program. Draws: embeddings
N(0, 0.02); matrices truncated normal (+-3 sd) with sd = fan_in ** -0.5;
QKV biases N(0, 0.1) where the model has them; norm scales 1. All
float32, the type the program stores and serves weights in (it casts to
bfloat16 at each use).

Reference. What a deployed plan (cut ``p``, per-layer weight bits
``bits_w``, hop bits ``bits_x``) computes, written out in float32
``jax.numpy`` at ``highest`` matmul precision, one layer at a time. It
imports nothing of the program and takes nothing the program made; of
the plan it takes the deployed cut and bit-widths, which define the
function requested. Per layer l:

* l < p (device segment): every weight tensor of the layer (projections,
  biases, norm scales) fake-quantized at ``bits_w[l]`` on a per-tensor
  asymmetric grid (``bench/core/reference.py``). Its keys and values are
  read back through the device cache's storage type, float8 e4m3 where
  ``bits_x <= 8``.
* after layer p - 1 (the hop): the hidden state quantized at ``bits_x``
  on one grid per position (min and max over the hidden dimension).
* l >= p (server tail): full precision.

Then the final norm and the unembedding (the tied embedding where the
model ties it). Departures from the published model: none in the
mathematics; rms_norm_eps and rope_theta are the published ones. The
control (``compute="fp8"``) is the same function with every matmul's
two operands cast to float8 e4m3 (accumulated in float32): the precision
below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.core.reference import F8, fake_quant, mm, rmsnorm, rope
from bench.core.weights import make_published, pad_axis, pad_q, trunc

BIAS_SD = 0.1
LAYER_KEYS = ("norm1", "wq", "wk", "wv", "wo", "bq", "bk", "bv", "norm2",
              "w_gate", "w_up", "w_down")

# field of the program's ModelConfig <- (published config key, type)
CONFIG_FIELDS = {"num_layers": ("num_hidden_layers", int),
                 "d_model": ("hidden_size", int),
                 "num_heads": ("num_attention_heads", int),
                 "num_kv_heads": ("num_key_value_heads", int),
                 "d_ff": ("intermediate_size", int),
                 "vocab_size": ("vocab_size", int),
                 "rope_theta": ("rope_theta", float),
                 "tie_embeddings": ("tie_word_embeddings", bool),
                 "qkv_bias": ("attention_bias", bool)}


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


def program_fields(m: dict) -> dict:
    """The program's ModelConfig fields that carry the config file's
    sizes; every other field keeps the program's default."""
    fields = {f: t(m[k]) for f, (k, t) in CONFIG_FIELDS.items() if k in m}
    fields["head_dim"] = dims(m)["hd"]
    return fields


def rehearsal(m: dict, widths: str) -> dict:
    """The model's stand-in for a CPU rehearsal: two layers at toy widths
    (``"toy"``) or four at the published widths (``"wide"``)."""
    m = dict(m)
    if widths == "toy":
        m.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                 num_key_value_heads=2 if m["num_key_value_heads"]
                 < m["num_attention_heads"] else 4,
                 head_dim=16, intermediate_size=128, vocab_size=512)
    else:
        m.update(num_hidden_layers=4)
    return m


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
         "wq": trunc(next(ks), (L, D, H, hd), D),
         "wk": trunc(next(ks), (L, D, KV, hd), D),
         "wv": trunc(next(ks), (L, D, KV, hd), D),
         "wo": trunc(next(ks), (L, H, hd, D), H * hd),
         "w_gate": trunc(next(ks), (L, D, F), D),
         "w_up": trunc(next(ks), (L, D, F), D),
         "w_down": trunc(next(ks), (L, F, D), F)}
    if not n["tied"]:
        w["lm_head"] = 0.02 * jax.random.normal(next(ks), (D, V),
                                                jnp.float32)
    if n["bias"]:
        w["bq"] = BIAS_SD * jax.random.normal(next(ks), (L, H, hd))
        w["bk"] = BIAS_SD * jax.random.normal(next(ks), (L, KV, hd))
        w["bv"] = BIAS_SD * jax.random.normal(next(ks), (L, KV, hd))
    return w


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
    attn = {"wq": pad_q(w["wq"], 2, kv, g, kvp, gp),
            "wk": pad_axis(w["wk"], 2, kvp),
            "wv": pad_axis(w["wv"], 2, kvp),
            "wo": pad_q(w["wo"], 1, kv, g, kvp, gp)}
    if n["bias"]:
        attn["bq"] = pad_q(w["bq"], 1, kv, g, kvp, gp)
        attn["bk"] = pad_axis(w["bk"], 1, kvp)
        attn["bv"] = pad_axis(w["bv"], 1, kvp)
    block = {"norm1": {"scale": w["norm1"]}, "attn": attn,
             "norm2": {"scale": w["norm2"]},
             "mlp": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                     "w_down": w["w_down"]}}
    params = {"embed": pad_axis(w["embed"], 0, vp),
              "final_norm": {"scale": w["final_norm"]},
              "blocks": [block]}
    if not n["tied"]:
        params["lm_head"] = pad_axis(w["lm_head"], 1, vp)
    return params


def weights_kind(dev_params) -> str:
    """How a device segment's tree carries its routed weights: "dense",
    or wire structs of "int8" or "int4" codes."""
    from repro.kernels import ops
    w = dev_params["blocks"][0]["mlp"]["w_up"]
    if not ops.is_wire_struct(w):
        return "dense"
    return "int4" if "codes_packed" in w else "int8"


# -- work counts: useful operations at published head counts ------------

def decode_flops(n: dict, context: int) -> float:
    """Operations of one decode step at live context c (multiply-add =
    2), each layer once and one unembedding:
      2 * L * (D*H*hd + 2*D*KV*hd + H*hd*D + 3*D*F) + 2*D*V
      + 4 * L * H * hd * c"""
    L, D, H, KV, hd, F, V = (n[k] for k in ("L", "D", "H", "KV", "hd",
                                            "F", "V"))
    per_layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    return 2.0 * L * per_layer + 2.0 * D * V + 4.0 * L * H * hd * context


def routed_matmuls(n: dict) -> list:
    """(K, N) of the matmuls of one device layer that run through
    ``qmatmul``: the seven projection and MLP matmuls."""
    D, H, KV, hd, F = (n[k] for k in ("D", "H", "KV", "hd", "F"))
    return [(D, H * hd), (D, KV * hd), (D, KV * hd), (H * hd, D),
            (D, F), (D, F), (F, D)]


def attention_layers(n: dict) -> range:
    """The layers that hold attention: every one."""
    return range(n["L"])


# -- the plain reference -------------------------------------------------

def _layer(x, lw, dev, bits, kv8, *, n, compute):
    """One decoder layer over the whole (padded) sequence x (S, D)."""
    lw = {k: jnp.where(dev, fake_quant(v, bits), v) for k, v in lw.items()}
    s = x.shape[0]
    pos = jnp.arange(s)
    kv, g, hd = n["KV"], n["H"] // n["KV"], n["hd"]
    h = rmsnorm(x, lw["norm1"], n["eps"])
    q = mm("sd,dhk->shk", h, lw["wq"], compute)
    k = mm("sd,dhk->shk", h, lw["wk"], compute)
    v = mm("sd,dhk->shk", h, lw["wv"], compute)
    if n["bias"]:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    q = rope(q, pos, n["theta"])
    k = rope(k, pos, n["theta"])
    cache8 = dev & kv8
    k = jnp.where(cache8, k.astype(F8).astype(jnp.float32), k)
    v = jnp.where(cache8, v.astype(F8).astype(jnp.float32), v)
    q = q.reshape(s, kv, g, hd)
    sc = mm("qkgd,tkd->kgqt", q, k, compute) * hd ** -0.5
    causal = pos[:, None] >= pos[None, :]
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = mm("kgqt,tkd->qkgd", p, v, compute).reshape(s, kv * g, hd)
    x = x + mm("shk,hkd->sd", o, lw["wo"], compute)
    h2 = rmsnorm(x, lw["norm2"], n["eps"])
    a = mm("sd,df->sf", h2, lw["w_gate"], compute)
    u = mm("sd,df->sf", h2, lw["w_up"], compute)
    return x + mm("sf,fd->sd", jax.nn.silu(a) * u, lw["w_down"], compute)


def _head(x, rows, final_norm, head, *, n, compute):
    h = rmsnorm(x[rows], final_norm, n["eps"])
    return mm("sd,dv->sv", h, head, compute)


class Reference:
    """The reference for one model and seed. ``logits`` runs the
    function of a plan over one token sequence and returns the logits at
    the rows asked for."""

    def __init__(self, model: dict, seed: int, seq_pad: int, rows_pad: int):
        self.n = dims(model)
        self.w = make_published(seed, model, published)
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
