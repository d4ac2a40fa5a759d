"""Decoder-stack assembly for every assigned architecture.

The stack is organized as ``num_periods`` repetitions of a ``period`` of
blocks (period = lcm(attn interleave, MoE interleave); 1 for homogeneous
stacks, 8 for Jamba). Parameters for each period-position are stacked over
periods and the stack runs under ``jax.lax.scan``, so HLO size — and
compile time for the 80-layer/72B dry-runs — is independent of depth.

Three entry points mirror the input-shape suite:
  ``forward``      train/prefill logits over a full sequence,
  ``prefill``      forward + returns the populated decode cache,
  ``decode_step``  one token against the cache (attention ring buffer /
                   SSM state), the body ``serve_step`` lowers.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, ModelConfig
from repro.kernels import ref
from repro.models import rope as rope_lib
from repro.models.attention import (attention_decode, attention_forward,
                                    attn_init, init_kv_cache)
from repro.models.common import embed_init, norm_apply, norm_init
from repro.models.mlp import mlp_apply, mlp_init
from repro.models.moe import moe_apply, moe_init
from repro.models.ssm import init_ssm_cache, ssm_decode, ssm_forward, ssm_init


def period_len(cfg: ModelConfig) -> int:
    a = cfg.attn_every if cfg.attn_every > 1 else 1
    m = cfg.moe.every if cfg.moe is not None else 1
    return math.lcm(a, m)


def num_periods(cfg: ModelConfig) -> int:
    p = period_len(cfg)
    assert cfg.num_layers % p == 0, (cfg.num_layers, p)
    return cfg.num_layers // p


# ---------------------------------------------------------------------------
# Init

def _block_init(key, cfg: ModelConfig, pos: int):
    ks = jax.random.split(key, 4)
    p = {"norm1": norm_init(cfg.norm, cfg.d_model)}
    if cfg.block_kind(pos) == ATTN:
        p["attn"] = attn_init(ks[0], cfg)
    else:
        p["ssm"] = ssm_init(ks[0], cfg)
    if cfg.uses_moe(pos):
        p["norm2"] = norm_init(cfg.norm, cfg.d_model)
        p["moe"] = moe_init(ks[1], cfg)
    elif cfg.d_ff:
        p["norm2"] = norm_init(cfg.norm, cfg.d_model)
        p["mlp"] = mlp_init(ks[1], cfg)
    return p


def init_params(key, cfg: ModelConfig):
    plen, nper = period_len(cfg), num_periods(cfg)
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    vp = cfg.padded_vocab()
    params = {"embed": embed_init(k_embed, (vp, cfg.d_model)),
              "final_norm": norm_init(cfg.norm, cfg.d_model)}
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(k_head, (cfg.d_model, vp))
    layer_keys = jax.random.split(k_layers, (plen, nper))
    blocks = []
    for pos in range(plen):
        stacked = jax.vmap(lambda k, pos=pos: _block_init(k, cfg, pos))(layer_keys[pos])
        blocks.append(stacked)
    params["blocks"] = blocks
    return params


def param_shapes(cfg: ModelConfig):
    """ShapeDtypeStruct tree without allocating (for the dry-run)."""
    return jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))


# ---------------------------------------------------------------------------
# Caches

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Per-period-position stacked caches (leading axis = num_periods)."""
    plen, nper = period_len(cfg), num_periods(cfg)

    def one(pos):
        if cfg.block_kind(pos) == ATTN:
            c = init_kv_cache(cfg, batch, max_len, dtype)
        else:
            c = init_ssm_cache(cfg, batch, dtype)
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (nper,) + x.shape), c)

    return [one(pos) for pos in range(plen)]


# ---------------------------------------------------------------------------
# Block application

# matmul-weight keys with a dequantize-fused kernel route: wire structs
# at these positions pass through _dequant_block intact and execute via
# ops.qdense (Pallas qmatmul/qmatmul4) inside attention/mlp. Everything
# else (MoE expert stacks, SSM mixers) still dequantizes at block entry.
KERNEL_ROUTED = {"attn": ("wq", "wk", "wv", "wo"),
                 "mlp": ("w_gate", "w_up", "w_down")}


def _dequant_block(bp, cfg):
    """Serving path: block weights may arrive as int8/int4 wire structs
    {codes|codes_packed, scale, mu} (core.quantizer) — the QPART
    quantization keeping weights compact in HBM. Structs under
    ``KERNEL_ROUTED`` positions are left packed: the qmatmul kernels
    dequantize per (block_k, block_n) tile inside the matmul
    (kernels/qmatmul.py), so the full-precision weight never
    materializes in HBM. Remaining structs dequantize here, once per
    block application."""
    def dequant(node):
        if "codes" in node:
            w = node["codes"].astype(jnp.float32) * node["scale"] \
                + node["mu"]
            return w.astype(getattr(jnp, cfg.dtype))
        # int4: two codes per byte, row halves (kernels.ref.pack_int4_ref)
        w = ref.unpack_int4_ref(node["codes_packed"]).astype(jnp.float32)
        w = w * node["scale"] + node["mu"]
        return w.astype(getattr(jnp, cfg.dtype))

    def is_struct(node):
        return isinstance(node, dict) and \
            ("codes" in node or "codes_packed" in node) and "scale" in node

    def walk(node, parent=None):
        if isinstance(node, dict):
            if is_struct(node):
                return node if parent == "routed" else dequant(node)
            out = {}
            for k, v in node.items():
                if parent in KERNEL_ROUTED and k in KERNEL_ROUTED[parent]:
                    out[k] = walk(v, "routed")
                else:
                    out[k] = walk(v, k)
            return out
        return node

    return walk(bp)


def _block_apply(bp, cfg, pos, x, positions, *, cache=None, decode_pos=None):
    """One block. Returns (x, aux, new_cache)."""
    bp = _dequant_block(bp, cfg)
    aux = None
    h = norm_apply(cfg.norm, bp["norm1"], x)
    if cfg.block_kind(pos) == ATTN:
        if cache is not None:
            mixed, cache = attention_decode(bp["attn"], cfg, h, cache, decode_pos)
        else:
            mixed = attention_forward(bp["attn"], cfg, h, positions)
    else:
        if cache is not None:
            mixed, cache = ssm_decode(bp["ssm"], cfg, h, cache)
        else:
            mixed = ssm_forward(bp["ssm"], cfg, h)
    x = x + mixed
    if "moe" in bp:
        h2 = norm_apply(cfg.norm, bp["norm2"], x)
        out, aux = moe_apply(bp["moe"], cfg, h2)
        x = x + out
    elif "mlp" in bp:
        h2 = norm_apply(cfg.norm, bp["norm2"], x)
        x = x + mlp_apply(bp["mlp"], cfg, h2)
    return x, aux, cache


def _zero_aux():
    return {"lb_loss": jnp.zeros((), jnp.float32),
            "z_loss": jnp.zeros((), jnp.float32),
            "dropped_frac": jnp.zeros((), jnp.float32)}


def _acc_aux(acc, aux):
    if aux is None:
        return acc
    return jax.tree.map(jnp.add, acc, aux)


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)

def _embed(params, cfg, tokens=None, embeds=None):
    if embeds is not None:
        return embeds.astype(getattr(jnp, cfg.dtype))
    return params["embed"][tokens].astype(getattr(jnp, cfg.dtype))


def _unembed(params, cfg, x):
    x = norm_apply(cfg.norm, params["final_norm"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    vp = cfg.padded_vocab()
    if vp != cfg.vocab_size:                  # mask padded vocab columns
        col = jnp.arange(vp)
        logits = jnp.where(col < cfg.vocab_size, logits, -1e30)
    return logits


def forward(params, cfg: ModelConfig, tokens=None, *, embeds=None,
            positions=None, remat: bool = False):
    """-> (logits (B,S,V), aux dict of summed router losses)."""
    x = _embed(params, cfg, tokens, embeds)
    b, s, _ = x.shape
    if positions is None:
        positions = rope_lib.text_positions(b, s)
    plen = period_len(cfg)

    def period_fn(x, period_params):
        aux_acc = _zero_aux()
        for pos in range(plen):
            x, aux, _ = _block_apply(period_params[pos], cfg, pos, x, positions)
            aux_acc = _acc_aux(aux_acc, aux)
        return x, aux_acc

    if remat:
        period_fn = jax.checkpoint(period_fn)

    def scan_fn(x, period_params):
        return period_fn(x, period_params)

    x, auxs = jax.lax.scan(scan_fn, x, tuple(params["blocks"]))
    aux = jax.tree.map(lambda a: a.sum(0), auxs)
    return _unembed(params, cfg, x), aux


def prefill(params, cfg: ModelConfig, tokens=None, *, embeds=None,
            positions=None, max_len: int, cache_dtype=jnp.bfloat16):
    """Forward + build the decode cache by replaying K/V (attention) and
    final states (SSM). Implemented as forward with per-block cache fill."""
    x = _embed(params, cfg, tokens, embeds)
    b, s, _ = x.shape
    if positions is None:
        positions = rope_lib.text_positions(b, s)
    plen = period_len(cfg)
    cache0 = init_cache(cfg, b, max_len, cache_dtype)

    def scan_fn(x, inp):
        period_params, caches = inp
        new_caches = []
        aux_acc = _zero_aux()
        for pos in range(plen):
            bp = _dequant_block(period_params[pos], cfg)
            h = norm_apply(cfg.norm, bp["norm1"], x)
            if cfg.block_kind(pos) == ATTN:
                mixed, c = _attn_prefill_with_cache(bp["attn"], cfg, h,
                                                    positions, caches[pos])
            else:
                mixed, c = _ssm_prefill_with_cache(bp["ssm"], cfg, h, caches[pos])
            x = x + mixed
            if "moe" in bp:
                h2 = norm_apply(cfg.norm, bp["norm2"], x)
                out, aux = moe_apply(bp["moe"], cfg, h2)
                x = x + out
                aux_acc = _acc_aux(aux_acc, aux)
            elif "mlp" in bp:
                h2 = norm_apply(cfg.norm, bp["norm2"], x)
                x = x + mlp_apply(bp["mlp"], cfg, h2)
            new_caches.append(c)
        return x, (tuple(new_caches), aux_acc)

    x, (caches, auxs) = jax.lax.scan(scan_fn, x, (tuple(params["blocks"]),
                                                  tuple(cache0)))
    aux = jax.tree.map(lambda a: a.sum(0), auxs)
    return _unembed(params, cfg, x), list(caches), aux


def _attn_prefill_with_cache(ap, cfg, h, positions, cache):
    from repro.models.attention import (_blocked_causal_attention,
                                        _out_proj, _project_qkv,
                                        _windowed_attention, DEFAULT_BLOCK_Q,
                                        DEFAULT_BLOCK_K)
    b, s, _ = h.shape
    q, k, v = _project_qkv(ap, cfg, h)
    qr = rope_lib.apply_rope(cfg.rope, q, positions, cfg.rope_theta)
    kr = rope_lib.apply_rope(cfg.rope, k, positions, cfg.rope_theta)
    bq, bk = min(DEFAULT_BLOCK_Q, s), min(DEFAULT_BLOCK_K, s)
    if cfg.sliding_window is not None and s > cfg.sliding_window:
        out = _windowed_attention(qr, kr, v, cfg.sliding_window, bq)
    else:
        out = _blocked_causal_attention(qr, kr, v, bq, bk)
    out = _out_proj(ap, cfg, out, h.dtype)
    buf = cache["k"].shape[1]
    # write the last min(s, buf) keys/values into the ring
    take = min(s, buf)
    kw = kr[:, s - take:, :, :].astype(cache["k"].dtype)
    vw = v[:, s - take:, :, :].astype(cache["v"].dtype)
    if take == buf:
        # ring layout: slot = pos % buf
        pos0 = s - take
        # jnp.roll: out[j] = in[(j - shift) % buf]; we need out[(pos0+i)%buf]
        # = in[i], i.e. shift = pos0.
        ck = jnp.roll(kw, pos0 % buf, axis=1)
        cv = jnp.roll(vw, pos0 % buf, axis=1)
    else:
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], kw, s - take, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], vw, s - take, axis=1)
    return out, {"k": ck, "v": cv}


def _ssm_prefill_with_cache(sp, cfg, h, cache):
    from repro.models.ssm import ssm_prefill
    return ssm_prefill(sp, cfg, h, cache)


# ---------------------------------------------------------------------------
# Decode

def decode_step(params, cfg: ModelConfig, token, caches, pos):
    """token (B,1) int32 or embeds (B,1,D); pos scalar int32 absolute
    position. Returns (logits (B,1,V), new caches)."""
    if token.ndim == 2:
        x = _embed(params, cfg, token)
    else:
        x = token.astype(getattr(jnp, cfg.dtype))
    plen = period_len(cfg)

    def scan_fn(x, inp):
        period_params, caches_in = inp
        new_caches = []
        for p in range(plen):
            x, _, c = _block_apply(period_params[p], cfg, p, x, None,
                                   cache=caches_in[p], decode_pos=pos)
            new_caches.append(c)
        return x, tuple(new_caches)

    x, caches = jax.lax.scan(scan_fn, x, (tuple(params["blocks"]),
                                          tuple(caches)))
    return _unembed(params, cfg, x), list(caches)


# ---------------------------------------------------------------------------
# Depth-independent segment forward (compile-once partitioned execution)

def segment_forward(params, cfg: ModelConfig, h, start, stop, *,
                    positions=None, collect: bool = False):
    """Apply blocks ``[start, stop)`` of the stack to hidden state ``h``
    (B, S, D) under ONE masked ``lax.scan`` over the stacked period
    representation. ``start``/``stop`` are DYNAMIC operands — every
    device/server segment split of the same input shape shares a single
    compiled program, instead of one XLA compilation per resume point.

    Every block of the stack is computed and blocks outside the segment
    are masked to identity (``jnp.where``): O(L) FLOPs regardless of
    segment length, O(1) compilations regardless of L — the QPART serving
    paths (calibration probes at every layer, arbitrary partition points)
    are compile-bound, not FLOP-bound, at the depths they sweep.

    ``collect=True`` additionally stacks the activation ENTERING each
    block — shape (L, B, S, D), the Alg. 1 calibration's ``acts`` — at
    the cost of the extra output buffer. Returns ``h_out`` or
    ``(h_out, acts)``. Router aux losses are dropped (serving paths only
    consume logits)."""
    b, s, _ = h.shape
    if positions is None:
        positions = rope_lib.text_positions(b, s)
    plen, nper = period_len(cfg), num_periods(cfg)
    start = jnp.asarray(start, jnp.int32)
    stop = jnp.asarray(stop, jnp.int32)

    def scan_fn(x, inp):
        per_idx, period_params = inp
        entries = []
        for pos in range(plen):
            layer = per_idx * plen + pos
            if collect:
                entries.append(x)
            x_new, _, _ = _block_apply(period_params[pos], cfg, pos, x,
                                       positions)
            active = (layer >= start) & (layer < stop)
            x = jnp.where(active, x_new, x)
        return x, (jnp.stack(entries) if collect else None)

    xs = (jnp.arange(nper), tuple(params["blocks"]))
    h, acts = jax.lax.scan(scan_fn, h, xs)
    if collect:
        # (nper, plen, B, S, D) -> (L, B, S, D); layer = per * plen + pos
        return h, acts.reshape((nper * plen,) + acts.shape[2:])
    return h


def segment_logits(params, cfg: ModelConfig, h, start, stop, *,
                   positions=None):
    """``segment_forward`` + unembed at the LAST position — the
    (B, V) "logits" view the serving backends and Alg. 1 probes use."""
    h = segment_forward(params, cfg, h, start, stop, positions=positions)
    return _unembed(params, cfg, h)[:, -1, :]


# ---------------------------------------------------------------------------
# Depth-independent segment prefill/decode (cut-point-partitioned KV cache)

def segment_prefill(params, cfg: ModelConfig, h, cache0, start, stop, *,
                    positions=None):
    """``prefill`` restricted to blocks ``[start, stop)`` under the same
    masked ``lax.scan`` as ``segment_forward`` — ``start``/``stop`` are
    DYNAMIC operands, so the device segment ``[0, p)`` and the server
    tail ``[p, L)`` of EVERY cut point share one compiled program per
    input shape. ``cache0`` is an ``init_cache`` tree (its dtype/max_len
    are part of the jit shape key); blocks outside the segment leave
    both the hidden state and their cache slots untouched
    (``jnp.where`` on every leaf). Returns ``(h_out, caches)``. Router
    aux losses are dropped (serving paths only consume logits)."""
    b, s, _ = h.shape
    if positions is None:
        positions = rope_lib.text_positions(b, s)
    plen, nper = period_len(cfg), num_periods(cfg)
    start = jnp.asarray(start, jnp.int32)
    stop = jnp.asarray(stop, jnp.int32)

    def scan_fn(x, inp):
        per_idx, period_params, caches = inp
        new_caches = []
        for pos in range(plen):
            layer = per_idx * plen + pos
            bp = _dequant_block(period_params[pos], cfg)
            hh = norm_apply(cfg.norm, bp["norm1"], x)
            if cfg.block_kind(pos) == ATTN:
                mixed, c = _attn_prefill_with_cache(bp["attn"], cfg, hh,
                                                    positions, caches[pos])
            else:
                mixed, c = _ssm_prefill_with_cache(bp["ssm"], cfg, hh,
                                                   caches[pos])
            x_new = x + mixed
            if "moe" in bp:
                h2 = norm_apply(cfg.norm, bp["norm2"], x_new)
                out, _ = moe_apply(bp["moe"], cfg, h2)
                x_new = x_new + out
            elif "mlp" in bp:
                h2 = norm_apply(cfg.norm, bp["norm2"], x_new)
                x_new = x_new + mlp_apply(bp["mlp"], cfg, h2)
            active = (layer >= start) & (layer < stop)
            x = jnp.where(active, x_new, x)
            new_caches.append(jax.tree.map(
                lambda new, old: jnp.where(active, new, old),
                c, caches[pos]))
        return x, tuple(new_caches)

    xs = (jnp.arange(nper), tuple(params["blocks"]), tuple(cache0))
    h, caches = jax.lax.scan(scan_fn, h, xs)
    return h, list(caches)


def segment_decode_step(params, cfg: ModelConfig, x, caches, pos, start,
                        stop):
    """One decode step over blocks ``[start, stop)``: ``x`` (B, 1, D)
    hidden state entering block ``start``, ``pos`` the scalar absolute
    position of the token. Masked twin of ``decode_step``'s scan with
    DYNAMIC ``(start, stop)``; inactive blocks pass hidden state and
    cache through unchanged. Returns ``(x_out, caches)``."""
    plen, nper = period_len(cfg), num_periods(cfg)
    start = jnp.asarray(start, jnp.int32)
    stop = jnp.asarray(stop, jnp.int32)

    def scan_fn(x, inp):
        per_idx, period_params, caches_in = inp
        new_caches = []
        for p in range(plen):
            layer = per_idx * plen + p
            x_new, _, c = _block_apply(period_params[p], cfg, p, x, None,
                                       cache=caches_in[p], decode_pos=pos)
            active = (layer >= start) & (layer < stop)
            x = jnp.where(active, x_new, x)
            new_caches.append(jax.tree.map(
                lambda new, old: jnp.where(active, new, old),
                c, caches_in[p]))
        return x, tuple(new_caches)

    xs = (jnp.arange(nper), tuple(params["blocks"]), tuple(caches))
    x, caches = jax.lax.scan(scan_fn, x, xs)
    return x, list(caches)


def _attn_extend_with_cache(ap, cfg, h, positions, cache):
    """Multi-token attention against a PARTIALLY POPULATED ring cache:
    project/RoPE the ``s`` incoming rows at absolute ``positions``, write
    their K/V into the cache, then attend every row against the full
    ring under a per-row validity mask (ring index <= row position).
    Masked lanes contribute EXACT zeros (``exp(NEG_INF - m) == 0.0`` and
    ``0.0 * v == 0.0``), so the reduction over the padded ring is
    bitwise the reduction over just the valid prefix — the body below is
    the single-block ``_blocked_causal_attention`` accumulator math with
    its initial carries written out (m0 = NEG_INF, l0 = 0, acc0 = 0:
    ``corr`` underflows to exact 0.0, so l = p.sum and acc = pv).

    Chunked prefill is therefore bitwise the monolithic
    ``segment_prefill`` for lossless cache storage and chunks of >= 2
    rows within one causal block (s <= DEFAULT_BLOCK_K): XLA lowers a
    1-row chunk's dense contractions to matvecs whose reduction order
    differs from the matmul the monolithic path ran, so chunk planners
    must never emit a size-1 chunk (``DecodeSession`` folds a remainder
    of 1 into the final chunk).

    No-wraparound contract: callers guarantee ``positions < buf`` (the
    decode sessions gate out sliding-window configs and bound positions
    by ``max_len``), so slot == position and the write is one
    ``dynamic_update_slice``.
    """
    from repro.models.attention import NEG_INF, _out_proj, _project_qkv
    b, s, _ = h.shape
    buf = cache["k"].shape[1]
    q, k, v = _project_qkv(ap, cfg, h)
    qr = rope_lib.apply_rope(cfg.rope, q, positions, cfg.rope_theta)
    kr = rope_lib.apply_rope(cfg.rope, k, positions, cfg.rope_theta)
    pos0 = positions[0, 0]
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], kr.astype(cache["k"].dtype), pos0, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), pos0, axis=1)
    hd = qr.shape[-1]
    kb = ck.astype(qr.dtype)
    vb = cv.astype(qr.dtype)
    qp = positions[0]                        # (s,) absolute row positions
    kp = jnp.arange(buf, dtype=positions.dtype)   # ring index == position
    mask = qp[:, None] >= kp[None, :]        # (s, buf) causal validity
    scale = hd ** -0.5
    sc = jnp.einsum("bqkgd,bskd->bkgqs", qr, kb,
                    preferred_element_type=jnp.float32) * scale
    sc = jnp.where(mask[None, None, None], sc, NEG_INF)
    m0 = jnp.full((b, kb.shape[2], qr.shape[3], s), NEG_INF, jnp.float32)
    m_new = jnp.maximum(m0, sc.max(axis=-1))
    p = jnp.exp(sc - m_new[..., None])
    corr = jnp.exp(m0 - m_new)
    l = jnp.zeros_like(m0) * corr + p.sum(axis=-1)
    pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vb.dtype), vb,
                    preferred_element_type=jnp.float32)
    acc = jnp.zeros_like(pv) * corr[..., None] + pv
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).astype(qr.dtype)
    out = _out_proj(ap, cfg, out, h.dtype)
    return out, {"k": ck, "v": cv}


def segment_extend(params, cfg: ModelConfig, h, caches, pos0, start, stop):
    """Apply blocks ``[start, stop)`` to ``s`` NEW rows ``h`` (B, S, D)
    entering at absolute position ``pos0``, extending the per-block ring
    caches in place of re-running the whole prefix. The masked-scan twin
    of ``segment_prefill`` with a POSITION OFFSET: ``pos0``/``start``/
    ``stop`` are dynamic operands, so the program is shape-keyed on the
    CHUNK length, never the prompt length — every chunk of every prompt
    reuses one compiled program per (batch, s) shape, and chunked
    prefill rebuilds a bit-identical cache vs the monolithic
    ``segment_prefill`` (see :func:`_attn_extend_with_cache` for the
    exact conditions). Attention blocks only — SSM state is a running
    reduction, not position-addressable, so a chunk cannot resume it
    mid-stream."""
    plen, nper = period_len(cfg), num_periods(cfg)
    for pos in range(plen):
        if cfg.block_kind(pos) != ATTN:
            raise NotImplementedError(
                "segment_extend supports attention blocks only: "
                f"block kind at period position {pos} is not ATTN")
    b, s, _ = h.shape
    positions = rope_lib.text_positions(b, s) + jnp.asarray(pos0, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    stop = jnp.asarray(stop, jnp.int32)

    def scan_fn(x, inp):
        per_idx, period_params, caches_in = inp
        new_caches = []
        for pos in range(plen):
            layer = per_idx * plen + pos
            bp = _dequant_block(period_params[pos], cfg)
            hh = norm_apply(cfg.norm, bp["norm1"], x)
            mixed, c = _attn_extend_with_cache(bp["attn"], cfg, hh,
                                               positions, caches_in[pos])
            x_new = x + mixed
            if "moe" in bp:
                h2 = norm_apply(cfg.norm, bp["norm2"], x_new)
                out, _ = moe_apply(bp["moe"], cfg, h2)
                x_new = x_new + out
            elif "mlp" in bp:
                h2 = norm_apply(cfg.norm, bp["norm2"], x_new)
                x_new = x_new + mlp_apply(bp["mlp"], cfg, h2)
            active = (layer >= start) & (layer < stop)
            x = jnp.where(active, x_new, x)
            new_caches.append(jax.tree.map(
                lambda new, old: jnp.where(active, new, old),
                c, caches_in[pos]))
        return x, tuple(new_caches)

    xs = (jnp.arange(nper), tuple(params["blocks"]), tuple(caches))
    h, caches = jax.lax.scan(scan_fn, h, xs)
    return h, list(caches)


def segment_verify(params, cfg: ModelConfig, xs, caches, pos0, start, stop):
    """Speculative-decode verification: run the ``s`` hidden rows ``xs``
    (B, S, D) — the cut-point activations of a drafted token batch at
    positions ``pos0 .. pos0 + s - 1`` — through blocks ``[start, stop)``
    and unembed EVERY row. Returns ``(logits (B, S, V), caches)``.

    The rows execute as a ``lax.scan`` of the EXACT
    ``segment_decode_step`` + unembed per-token math, inside ONE jitted
    program: bit-identical logits to ``s`` sequential decode steps BY
    CONSTRUCTION (same ops, same shapes, same kernel routing — a
    guarantee a batched multi-row forward cannot make, since XLA's
    reduction order in the dense contractions differs between 1-row and
    s-row operands). What the batching buys is the SERVING shape: one
    device->server round trip verifies k drafts instead of k round
    trips, which is the term that bounds tokens/s on a slow channel.

    Attention blocks only: an SSM running state cannot be rolled back
    to the acceptance point when a draft is rejected, while a ring
    cache needs no rollback at all (every stale slot is re-written
    before any later query can attend it)."""
    plen = period_len(cfg)
    for pos in range(plen):
        if cfg.block_kind(pos) != ATTN:
            raise NotImplementedError(
                "segment_verify supports attention blocks only: "
                f"block kind at period position {pos} is not ATTN")
    b, s, _ = xs.shape
    pos0 = jnp.asarray(pos0, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    stop = jnp.asarray(stop, jnp.int32)

    def row_step(carry, inp):
        xj, off = inp                       # (B, D), scalar row offset
        x_out, new_caches = segment_decode_step(
            params, cfg, xj[:, None, :], list(carry), pos0 + off, start,
            stop)
        logits = _unembed(params, cfg, x_out)[:, -1, :]
        return tuple(new_caches), logits

    carry, logits = jax.lax.scan(
        row_step, tuple(caches),
        (xs.transpose(1, 0, 2), jnp.arange(s, dtype=jnp.int32)))
    return logits.transpose(1, 0, 2), list(carry)


# ---------------------------------------------------------------------------
# Public single-block entry points (repro.serving.backends.transformer):
# embed/unembed and one block application — the non-scan view of the same
# math `forward` runs under lax.scan, for paths that need per-block access
# (QPART noise calibration, partitioned device-segment execution).

embed_tokens = _embed
unembed = _unembed
apply_block = _block_apply


def block_at(params, cfg: ModelConfig, layer: int):
    """(block param pytree, period position) of global block index
    ``layer``: the scan iterates periods on the stacked leading axis and
    positions within a period, so layer = period * period_len + pos."""
    per, pos = divmod(layer, period_len(cfg))
    return jax.tree.map(lambda t: t[per], params["blocks"][pos]), pos
