"""Multi-head latent attention (DeepSeek-V2/V3), with the latent cache.

Per token x (D): the query is one projection to H heads of
``qk_nope + qk_rope`` (no query low-rank); ``wkv_a`` projects to a
latent of ``kv_lora_rank`` plus one ``qk_rope`` key shared by every
head; the latent is RMS-normed (``kv_norm``) and, through ``wkv_b``,
gives each head its ``qk_nope`` key and ``v_head_dim`` value. RoPE
(``cfg.rope``) rotates the query's rope part and the shared rope key.
Softmax scale (qk_nope + qk_rope) ** -0.5.

The cache holds, per token and layer, the normed latent (``c``) and the
rotated rope key (``kr``): ``cache_width`` elements, never K/V heads.

Attention runs in the absorbed form everywhere (forward, prefill through
the cache, decode): ``wkv_b``'s key half is folded into the query
(q_nope W_kb^T, a latent-width query per head) and its value half into
the output (softmax-weighted latents times W_vb), so scores are taken
against the cached latents directly and no step expands K/V over the
context. A decode step then reads ``cache_width`` elements per live
token, where the expanded form would read the cache and multiply it
through ``wkv_b`` (H * (qk_nope + v) per token). The absorbed form needs
``wkv_b`` per head, so it is dequantized at block entry on the
quantized path (kv_lora_rank * H * (qk_nope + v) parameters a layer);
``wq``, ``wkv_a`` and ``wo`` run through the qmatmul kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import rope as rope_lib
from repro.models.common import dense_init, rmsnorm, rmsnorm_init

NEG_INF = -1e30
# the latent's RMSNorm is built without the config's rms_norm_eps in the
# published modeling code (DeepseekV3RMSNorm's default), unlike the
# block norms
LATENT_NORM_EPS = 1e-6


def mla_init(key, cfg):
    a, d, h = cfg.mla, cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, h, a.qk_head_dim)),
        "wkv_a": dense_init(ks[1], (d, a.cache_width)),
        "kv_norm": rmsnorm_init(a.kv_lora_rank),
        "wkv_b": dense_init(ks[2], (a.kv_lora_rank, h,
                                    a.qk_nope_head_dim + a.v_head_dim)),
        "wo": dense_init(ks[3], (h, a.v_head_dim, d), in_axis=1),
    }


def init_mla_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    a = cfg.mla
    return {"c": jnp.zeros((batch, max_len, a.kv_lora_rank), dtype),
            "kr": jnp.zeros((batch, max_len, a.qk_rope_head_dim), dtype)}


def _proj(x, w):
    from repro.kernels import ops
    if ops.is_wire_struct(w):
        return ops.qdense(x, w)
    return jnp.einsum("bsd,d...->bs...", x, w.astype(x.dtype))


def _project(params, cfg, x, positions):
    """x (B,S,D) -> q_nope (B,S,H,dn), q_pe (B,S,H,dr), normed latent
    c (B,S,C), rotated rope key kr (B,S,dr)."""
    a = cfg.mla
    q = _proj(x, params["wq"])
    kv = _proj(x, params["wkv_a"])
    c = rmsnorm(params["kv_norm"], kv[..., :a.kv_lora_rank],
                LATENT_NORM_EPS)
    kr = rope_lib.apply_rope(cfg.rope, kv[..., None, a.kv_lora_rank:],
                             positions, cfg.rope_theta)[..., 0, :]
    q_pe = rope_lib.apply_rope(cfg.rope, q[..., a.qk_nope_head_dim:],
                               positions, cfg.rope_theta)
    return q[..., :a.qk_nope_head_dim], q_pe, c, kr


def _attend(params, cfg, q_nope, q_pe, c, kr, mask, dtype):
    """Absorbed attention of the query rows against latents ``c``
    (B,T,C) and rope keys ``kr`` (B,T,dr) under ``mask`` (S, T)."""
    a = cfg.mla
    wkv_b = params["wkv_b"].astype(dtype)
    w_kb = wkv_b[..., :a.qk_nope_head_dim]               # (C, H, dn)
    w_vb = wkv_b[..., a.qk_nope_head_dim:]               # (C, H, dv)
    f32 = jnp.float32
    q_lat = jnp.einsum("bshn,chn->bshc", q_nope, w_kb,
                       preferred_element_type=f32).astype(dtype)
    sc = jnp.einsum("bshc,btc->bhst", q_lat, c, preferred_element_type=f32)
    sc = sc + jnp.einsum("bshr,btr->bhst", q_pe, kr,
                         preferred_element_type=f32)
    sc = jnp.where(mask[None, None], sc * a.qk_head_dim ** -0.5, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    o_lat = jnp.einsum("bhst,btc->bhsc", p.astype(dtype), c,
                       preferred_element_type=f32).astype(dtype)
    o = jnp.einsum("bhsc,chv->bshv", o_lat, w_vb,
                   preferred_element_type=f32).astype(dtype)
    from repro.kernels import ops
    if ops.is_wire_struct(params["wo"]):
        return ops.qdense(o, params["wo"], n_contract=2, out_dtype=dtype)
    return jnp.einsum("bshv,hvd->bsd", o, params["wo"].astype(dtype))


def mla_forward(params, cfg, x, positions):
    """Causal MLA over a whole sequence, no cache. x (B,S,D)."""
    q_nope, q_pe, c, kr = _project(params, cfg, x, positions)
    s = x.shape[1]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    return _attend(params, cfg, q_nope, q_pe, c, kr, causal, x.dtype)


def mla_extend(params, cfg, x, positions, cache):
    """The rows x (B,S,D) at absolute ``positions`` (consecutive from
    positions[0, 0]) write their latents and rope keys into the cache,
    then attend through it: every row against the cache under a per-row
    validity mask (slot <= row position). The cache is read back in its
    storage dtype, as a later decode step reads it. Callers keep
    positions < the cache length (slot == position)."""
    q_nope, q_pe, c, kr = _project(params, cfg, x, positions)
    pos0 = positions[0, 0]
    cc = jax.lax.dynamic_update_slice_in_dim(
        cache["c"], c.astype(cache["c"].dtype), pos0, axis=1)
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache["kr"], kr.astype(cache["kr"].dtype), pos0, axis=1)
    mask = positions[0][:, None] >= jnp.arange(cc.shape[1])[None, :]
    out = _attend(params, cfg, q_nope, q_pe, cc.astype(x.dtype),
                  ck.astype(x.dtype), mask, x.dtype)
    return out, {"c": cc, "kr": ck}


def mla_decode(params, cfg, x, cache, pos):
    """One token x (B,1,D) at scalar position ``pos``."""
    positions = jnp.broadcast_to(pos, (x.shape[0], 1)).astype(jnp.int32)
    return mla_extend(params, cfg, x, positions, cache)
