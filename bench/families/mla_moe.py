"""The mla_moe family: the DeepSeek-V3 block (``model_type`` deepseek_v3)
as Moonlight-16B-A3B publishes it. Every layer has multi-head latent
attention; the first ``first_k_dense_replace`` layers a dense SwiGLU MLP
and the rest a routed-expert layer with shared experts. A chip holds a
share of each expert layer's routed experts (the configuration's
``deployment``), and this file computes that share.

What a family file provides is listed in ``bench/families/dense.py``;
this one adds the counts its own readers divide by: ``expert_matmuls``
(the (K, N) of one expert's three matmuls) and ``moe_layers``.

Equations, per layer, for a sequence x (S, D) (published config keys in
brackets):

* attention: h = RMSNorm(x) [rms_norm_eps]; q = h Wq, one projection to
  H heads of qk_nope + qk_rope [q_lora_rank null]; [c | k_pe] = h Wkv_a;
  the latent c (kv_lora_rank) is RMS-normed (eps 1e-6, the modeling
  code's default for this norm); per head, [k_nope | v] = c Wkv_b.
  RoPE [rope_theta] rotates the interleaved pairs (2i, 2i+1) of q's rope
  part and of k_pe, one rope key shared by every head. Scores
  (q_nope k_nope + q_pe k_pe) / sqrt(qk_nope + qk_rope), causal softmax,
  o = p v, x += o Wo. There is no rope_scaling, so no mscale.
* dense MLP (layers < first_k_dense_replace): SwiGLU of
  intermediate_size.
* routed experts: h = RMSNorm(x); scores s = sigmoid(h W_router) over all
  n_routed_experts (published count); the k = num_experts_per_tok
  experts of largest s + e_score_correction_bias are chosen (noaux_tc,
  n_group = topk_group = 1); gates are s of the chosen, divided by their
  sum [norm_topk_prob] and times routed_scaling_factor; x += sum over
  the chosen experts HELD HERE of gate * SwiGLU_e(h) [moe_intermediate_
  size] + the shared experts, one SwiGLU of n_shared_experts *
  moe_intermediate_size.

Then the final norm and the untied head, over this chip's slice of the
vocabulary (the config's vocab_size).

Weights (``published``): drawn as the dense family draws them (matrices
truncated normal +-3 sd with sd = fan_in ** -0.5; embeddings and head
N(0, 0.02); norm scales 1), the held experts only, and the trained
e_score_correction_bias drawn N(0, 0.1) so that it moves the selection.
All float32, the type the program stores weights in.

Reference. What a deployed plan computes, in float32 ``jax.numpy`` at
``highest`` precision, one layer at a time, importing nothing of the
program. For layers l < p every tensor (projections, the router and its
bias, the held experts' stacks, the shared experts, norm scales) is
fake-quantized at ``bits_w[l]`` on one asymmetric grid per tensor: the
held experts' stack of a layer shares one grid, as the program deploys
it (``_build_qstacked``, ``split_blocks``). The latent and the rope key
are read back through the device cache's storage type (float8 e4m3
where bits_x <= 8); after layer p - 1 the hidden state is quantized at
bits_x per position. Attention runs in the published (expanded) form;
the program runs the absorbed form, the same function up to rounding.
Departures from the published model: the experts and vocabulary rows
not held here are left out, in the program and here alike (the cut, see
the configuration's ``deployment``). The control (``compute="fp8"``)
casts both operands of every matmul, the router's included, to float8
e4m3.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.core.reference import F8, fake_quant, mm, rmsnorm
from bench.core.weights import make_published, trunc

BIAS_SD = 0.1
LATENT_EPS = 1e-6
ATTN_KEYS = ("norm1", "wq", "wkv_a", "kv_norm", "wkv_b", "wo")
DENSE_KEYS = ("d_gate", "d_up", "d_down")
MOE_KEYS = ("w_router", "e_bias", "w_gate", "w_up", "w_down", "s_gate",
            "s_up", "s_down")


def dims(m: dict) -> dict:
    """Published sizes under short names; ``E`` the experts the router
    scores (the published count), ``held`` those this chip holds from
    ``first_held`` on."""
    return {"L": m["num_hidden_layers"], "D": m["hidden_size"],
            "H": m["num_attention_heads"], "dn": m["qk_nope_head_dim"],
            "dr": m["qk_rope_head_dim"], "dv": m["v_head_dim"],
            "C": m["kv_lora_rank"], "F": m["intermediate_size"],
            "Fe": m["moe_intermediate_size"],
            "E": m["published"].get("n_routed_experts",
                                    m["n_routed_experts"]),
            "held": m["n_routed_experts"],
            "first_held": m["deployment"]["first_expert_held"],
            "k": m["num_experts_per_tok"], "shared": m["n_shared_experts"],
            "dense": m["first_k_dense_replace"], "V": m["vocab_size"],
            "scale": float(m["routed_scaling_factor"]),
            "norm_topk": bool(m["norm_topk_prob"]),
            "eps": float(m["rms_norm_eps"]),
            "theta": float(m["rope_theta"])}


def program_fields(m: dict) -> dict:
    """The program's ModelConfig fields for the config file's sizes."""
    from repro.configs.base import MLAConfig, MoEConfig
    n = dims(m)
    if m["scoring_func"] != "sigmoid" or m["topk_method"] != "noaux_tc" \
            or m["n_group"] != 1 or m["q_lora_rank"] is not None:
        raise ValueError("only sigmoid noaux_tc routing without groups and "
                         "a single query projection are laid out")
    return {"num_layers": n["L"], "d_model": n["D"], "num_heads": n["H"],
            "num_kv_heads": n["H"], "head_dim": n["dn"] + n["dr"],
            "d_ff": n["F"], "vocab_size": n["V"], "rope_theta": n["theta"],
            "rope": "rope_interleaved", "norm_eps": n["eps"],
            "tie_embeddings": bool(m["tie_word_embeddings"]),
            "mla": MLAConfig(kv_lora_rank=n["C"], qk_nope_head_dim=n["dn"],
                             qk_rope_head_dim=n["dr"], v_head_dim=n["dv"]),
            "moe": MoEConfig(num_experts=n["E"], top_k=n["k"],
                             d_ff=n["Fe"], score="sigmoid", expert_bias=True,
                             norm_topk=n["norm_topk"],
                             routed_scale=n["scale"], n_shared=n["shared"],
                             first_dense=n["dense"],
                             experts_held=n["held"],
                             first_held=n["first_held"], dropless=True)}


def rehearsal(m: dict, widths: str) -> dict:
    """Toy widths (``"toy"``: a dense layer and three expert layers, 4 of
    8 experts held, top-2, one shared) or two layers at the published
    widths (``"wide"``)."""
    m = dict(m)
    if widths == "toy":
        m.update(num_hidden_layers=4, hidden_size=64, num_attention_heads=4,
                 num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
                 qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
                 moe_intermediate_size=32, n_routed_experts=4,
                 num_experts_per_tok=2, n_shared_experts=1, vocab_size=512,
                 published=dict(m["published"], n_routed_experts=8),
                 deployment=dict(m["deployment"], first_expert_held=2))
    else:
        m.update(num_hidden_layers=2)
    return m


def published(key, m: dict) -> dict:
    """The held share of the published weights: attention stacked over
    all L layers, dense MLPs over the leading ``dense`` layers, expert
    layers over the rest."""
    n = dims(m)
    L, D, H, C, V = n["L"], n["D"], n["H"], n["C"], n["V"]
    dq, dr, dv, dn = n["dn"] + n["dr"], n["dr"], n["dv"], n["dn"]
    Ld, Lm, F, Fe, E, e = n["dense"], L - n["dense"], n["F"], n["Fe"], \
        n["E"], n["held"]
    Fs = n["shared"] * Fe
    ks = iter(jax.random.split(key, 20))
    return {
        "embed": 0.02 * jax.random.normal(next(ks), (V, D), jnp.float32),
        "lm_head": 0.02 * jax.random.normal(next(ks), (D, V), jnp.float32),
        "final_norm": jnp.ones((D,), jnp.float32),
        "norm1": jnp.ones((L, D), jnp.float32),
        "norm2": jnp.ones((L, D), jnp.float32),
        "kv_norm": jnp.ones((L, C), jnp.float32),
        "wq": trunc(next(ks), (L, D, H, dq), D),
        "wkv_a": trunc(next(ks), (L, D, C + dr), D),
        "wkv_b": trunc(next(ks), (L, C, H, dn + dv), C),
        "wo": trunc(next(ks), (L, H, dv, D), H * dv),
        "d_gate": trunc(next(ks), (Ld, D, F), D),
        "d_up": trunc(next(ks), (Ld, D, F), D),
        "d_down": trunc(next(ks), (Ld, F, D), F),
        "w_router": trunc(next(ks), (Lm, D, E), D),
        "e_bias": BIAS_SD * jax.random.normal(next(ks), (Lm, E)),
        "w_gate": trunc(next(ks), (Lm, e, D, Fe), D),
        "w_up": trunc(next(ks), (Lm, e, D, Fe), D),
        "w_down": trunc(next(ks), (Lm, e, Fe, D), Fe),
        "s_gate": trunc(next(ks), (Lm, D, Fs), D),
        "s_up": trunc(next(ks), (Lm, D, Fs), D),
        "s_down": trunc(next(ks), (Lm, Fs, D), Fs)}


def to_program(w: dict, m: dict, cfg) -> dict:
    """The program's parameter tree (``repro.models.transformer``: one
    period holding every layer, each block with a leading axis of 1)."""
    n = dims(m)
    nd = n["dense"]

    def one(x, i):
        return x[i:i + 1]

    blocks = []
    for layer in range(n["L"]):
        b = {"norm1": {"scale": one(w["norm1"], layer)},
             "attn": {"wq": one(w["wq"], layer),
                      "wkv_a": one(w["wkv_a"], layer),
                      "kv_norm": {"scale": one(w["kv_norm"], layer)},
                      "wkv_b": one(w["wkv_b"], layer),
                      "wo": one(w["wo"], layer)},
             "norm2": {"scale": one(w["norm2"], layer)}}
        if layer < nd:
            b["mlp"] = {"w_gate": one(w["d_gate"], layer),
                        "w_up": one(w["d_up"], layer),
                        "w_down": one(w["d_down"], layer)}
        else:
            i = layer - nd
            b["moe"] = {"w_router": one(w["w_router"], i),
                        "e_bias": one(w["e_bias"], i),
                        "w_gate": one(w["w_gate"], i),
                        "w_up": one(w["w_up"], i),
                        "w_down": one(w["w_down"], i),
                        "shared": {"w_gate": one(w["s_gate"], i),
                                   "w_up": one(w["s_up"], i),
                                   "w_down": one(w["s_down"], i)}}
        blocks.append(b)
    return {"embed": w["embed"], "lm_head": w["lm_head"],
            "final_norm": {"scale": w["final_norm"]}, "blocks": blocks}


def weights_kind(dev_params) -> str:
    """How a device segment carries its expert stacks: "dense", or wire
    structs of "int8" or "int4" codes (the last layer's, an expert
    layer; its attention and shared experts are carried alike)."""
    from repro.kernels import ops
    w = dev_params["blocks"][-1]["moe"]["w_up"]
    if not ops.is_wire_struct(w):
        return "dense"
    return "int4" if "codes_packed" in w else "int8"


# -- work counts: useful operations and matmuls of this chip's share ----

def _mla_ops(n: dict) -> float:
    """Multiply-adds of one token's attention projections, absorbed
    form (the two folds of wkv_b included)."""
    D, H, C, dn, dr, dv = (n[k] for k in ("D", "H", "C", "dn", "dr", "dv"))
    return D * H * (dn + dr) + D * (C + dr) + H * dn * C + H * C * dv \
        + H * dv * D


def _ffn_ops(n: dict, layer: int) -> float:
    """Multiply-adds of one token's feed-forward half: the dense MLP, or
    the router, the expected k * held / E held experts and the shared
    experts."""
    D = n["D"]
    if layer < n["dense"]:
        return 3.0 * D * n["F"]
    return D * n["E"] + (n["k"] * n["held"] / n["E"] + n["shared"]) \
        * 3.0 * D * n["Fe"]


def decode_flops(n: dict, context: int) -> float:
    """Operations of one decode step at live context c (multiply-add =
    2): each layer's attention projections and feed-forward half once,
    scores and the latent-weighted sum over the live context
    (H * (C + dr + C) per position), and the head over this chip's
    vocabulary slice."""
    per_ctx = n["H"] * (2 * n["C"] + n["dr"])
    total = sum(_mla_ops(n) + _ffn_ops(n, layer) + per_ctx * context
                for layer in range(n["L"]))
    return 2.0 * (total + n["D"] * n["V"])


def routed_matmuls(n: dict) -> list:
    """(K, N) of an expert layer's matmuls that run through ``qmatmul``:
    wq, wkv_a, wo and the shared experts' three (the dense layer's MLP
    runs there too, at its own width)."""
    D, H, C, dn, dr, dv = (n[k] for k in ("D", "H", "C", "dn", "dr", "dv"))
    Fs = n["shared"] * n["Fe"]
    return [(D, H * (dn + dr)), (D, C + dr), (H * dv, D), (D, Fs), (D, Fs),
            (Fs, D)]


def attention_layers(n: dict) -> range:
    """The layers that hold attention: every one."""
    return range(n["L"])


def moe_layers(n: dict) -> range:
    """The layers that hold routed experts."""
    return range(n["dense"], n["L"])


def expert_matmuls(n: dict) -> list:
    """(K, N) of one expert's three matmuls (gate, up, down)."""
    D, Fe = n["D"], n["Fe"]
    return [(D, Fe), (D, Fe), (Fe, D)]


# -- the plain reference -------------------------------------------------

def rope_pairs(x, pos, theta):
    """x (S, heads, d); the pairs (x[2i], x[2i+1]) rotate by pos * theta
    ** (-2i / d)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq            # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def _quant(lw, dev, bits):
    return {k: jnp.where(dev, fake_quant(v, bits), v) for k, v in lw.items()}


def _attention(x, lw, dev, bits, kv8, *, n, compute):
    """x + the layer's attention; returns (x, the post-attention norm's
    input)."""
    lw = _quant(lw, dev, bits)
    s = x.shape[0]
    pos = jnp.arange(s)
    C, dn, dq = n["C"], n["dn"], n["dn"] + n["dr"]
    h = rmsnorm(x, lw["norm1"], n["eps"])
    q = mm("sd,dhk->shk", h, lw["wq"], compute)
    kv = mm("sd,dc->sc", h, lw["wkv_a"], compute)
    c = rmsnorm(kv[:, :C], lw["kv_norm"], LATENT_EPS)
    k_pe = rope_pairs(kv[:, None, C:], pos, n["theta"])[:, 0]
    cache8 = dev & kv8
    c = jnp.where(cache8, c.astype(F8).astype(jnp.float32), c)
    k_pe = jnp.where(cache8, k_pe.astype(F8).astype(jnp.float32), k_pe)
    q_pe = rope_pairs(q[..., dn:], pos, n["theta"])
    kvb = mm("sc,chk->shk", c, lw["wkv_b"], compute)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    sc = (mm("qhn,thn->hqt", q[..., :dn], k_nope, compute)
          + mm("qhr,tr->hqt", q_pe, k_pe, compute)) * dq ** -0.5
    causal = pos[:, None] >= pos[None, :]
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = mm("hqt,thv->qhv", p, v, compute)
    return x + mm("qhv,hvd->qd", o, lw["wo"], compute)


def _swiglu(h, wg, wu, wd, compute):
    a = mm("sd,df->sf", h, wg, compute)
    u = mm("sd,df->sf", h, wu, compute)
    return mm("sf,fd->sd", jax.nn.silu(a) * u, wd, compute)


def _dense_ffn(x, lw, dev, bits, *, n, compute):
    lw = _quant(lw, dev, bits)
    h = rmsnorm(x, lw["norm2"], n["eps"])
    return x + _swiglu(h, lw["d_gate"], lw["d_up"], lw["d_down"], compute)


def route(h, w_router, e_bias, *, n, compute):
    """h (S, D) -> (ids (S, k) over all E experts, gates (S, k))."""
    s = jax.nn.sigmoid(mm("sd,de->se", h, w_router, compute))
    _, ids = jax.lax.top_k(s + e_bias, n["k"])
    g = jnp.take_along_axis(s, ids, -1)
    if n["norm_topk"]:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    return ids, g * n["scale"]


def _route_ids(x, lw, dev, bits, *, n):
    """The expert ids the layer's router chooses for x (S, D)."""
    lw = _quant(lw, dev, bits)
    h = rmsnorm(x, lw["norm2"], n["eps"])
    return route(h, lw["w_router"], lw["e_bias"], n=n, compute="f32")[0]


def _moe_ffn(x, lw, dev, bits, *, n, compute):
    lw = _quant(lw, dev, bits)
    h = rmsnorm(x, lw["norm2"], n["eps"])
    ids, g = route(h, lw["w_router"], lw["e_bias"], n=n, compute=compute)
    held = n["first_held"] + jnp.arange(n["held"])
    combine = jnp.einsum("sk,ske->se", g,
                         (ids[..., None] == held).astype(jnp.float32))
    y = jnp.stack([_swiglu(h, lw["w_gate"][e], lw["w_up"][e],
                           lw["w_down"][e], compute)
                   for e in range(n["held"])], 1)            # (S, e, D)
    out = jnp.einsum("se,sed->sd", combine, y)
    return x + out + _swiglu(h, lw["s_gate"], lw["s_up"], lw["s_down"],
                             compute)


def _head(x, rows, final_norm, head, *, n, compute):
    h = rmsnorm(x[rows], final_norm, n["eps"])
    return mm("sd,dv->sv", h, head, compute)


class Reference:
    """The reference for one model and seed (see the module docstring).
    ``logits`` runs the function of a plan over one token sequence and
    returns the logits at the rows asked for; ``routes`` gives the
    expert ids each expert layer chooses at those rows."""

    def __init__(self, model: dict, seed: int, seq_pad: int, rows_pad: int):
        self.n = dims(model)
        self.w = make_published(seed, model, published)
        self.seq_pad, self.rows_pad = seq_pad, rows_pad
        mk = {"attn": _attention, "dense": _dense_ffn, "moe": _moe_ffn,
              "head": _head}
        self._fn = {(name, c): jax.jit(functools.partial(f, n=self.n,
                                                         compute=c))
                    for name, f in mk.items() for c in ("f32", "fp8")}
        self._hop = jax.jit(lambda x, b: fake_quant(x, b, axis=-1))
        self._route = jax.jit(functools.partial(_route_ids, n=self.n))

    def _layer_weights(self, keys, index):
        return {k: self.w[k][index] for k in keys}

    def _run(self, tokens, p, bits_w, bits_x, compute, on_moe=None):
        s = len(tokens)
        if s > self.seq_pad:
            raise ValueError(f"sequence {s} exceeds the reference's "
                             f"padding")
        tok = np.zeros(self.seq_pad, np.int32)
        tok[:s] = tokens
        kv8 = jnp.asarray(0 < bits_x <= 8)
        x = self.w["embed"][jnp.asarray(tok)]
        n = self.n
        for layer in range(n["L"]):
            dev = jnp.asarray(layer < p)
            bits = jnp.float32(bits_w[layer] if layer < p else 16.0)
            x = self._fn["attn", compute](
                x, self._layer_weights(ATTN_KEYS, layer), dev, bits, kv8)
            if layer < n["dense"]:
                lw = self._layer_weights(DENSE_KEYS, layer)
                lw["norm2"] = self.w["norm2"][layer]
                x = self._fn["dense", compute](x, lw, dev, bits)
            else:
                lw = self._layer_weights(MOE_KEYS, layer - n["dense"])
                lw["norm2"] = self.w["norm2"][layer]
                if on_moe is not None:
                    on_moe(layer, x, lw, dev, bits)
                x = self._fn["moe", compute](x, lw, dev, bits)
            if layer == p - 1:
                x = self._hop(x, jnp.float32(bits_x))
        return x

    def logits(self, tokens: np.ndarray, rows: np.ndarray, p: int,
               bits_w, bits_x: int, compute: str = "f32") -> np.ndarray:
        """tokens (S,) ids; rows: the positions whose next-token logits
        are wanted -> (len(rows), V) float32."""
        if len(rows) > self.rows_pad:
            raise ValueError(f"rows {len(rows)} exceed the reference's "
                             f"padding")
        r = np.zeros(self.rows_pad, np.int32)
        r[:len(rows)] = rows
        with jax.default_matmul_precision("highest"):
            x = self._run(tokens, p, bits_w, bits_x, compute)
            out = self._fn["head", compute](x, jnp.asarray(r),
                                            self.w["final_norm"],
                                            self.w["lm_head"])
        return np.asarray(out, np.float32)[:len(rows)]

    def routes(self, tokens: np.ndarray, rows: np.ndarray, p: int, bits_w,
               bits_x: int) -> dict:
        """Expert layer -> (len(rows), k) expert ids the reference's
        router chooses at ``rows``."""
        out = {}

        def keep(layer, x, lw, dev, bits):
            out[layer] = np.asarray(self._route(x, lw, dev, bits))[rows]

        with jax.default_matmul_precision("highest"):
            self._run(tokens, p, bits_w, bits_x, "f32", keep)
        return out
