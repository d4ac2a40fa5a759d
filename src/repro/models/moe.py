"""Top-k mixture-of-experts: capacity-based einsum dispatch (training),
and the dropless routed-expert layer the served path runs for configs
that ask for it (``MoEConfig.dropless``; see ``moe_routed_apply``).

Mesh-TF / Switch-Transformer formulation: tokens are split into groups, a
dispatch one-hot of shape (G, GS, E, C) routes each token to at most k
expert-capacity slots, and two einsums move activations to expert-major
layout (E, G, C, D) and back. Under pjit with experts sharded on the
``model`` axis and groups on ``data``, XLA lowers the dispatch/combine
einsums to all-to-alls — the canonical expert-parallel schedule.

Group size is kept small (default 128 tokens) so the dispatch tensor stays
~`T·GS·k` elements: with GS=128 that is <2 bytes/token/capacity-slot of
bf16, a few MB per device at the assigned shapes.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.common import dense_init, silu

DEFAULT_GROUP_SIZE = 128


def moe_init(key, cfg):
    if cfg.moe.dropless:
        return moe_routed_init(key, cfg)
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff, m.num_experts
    ks = jax.random.split(key, 4)
    p = {"w_router": dense_init(ks[0], (d, e))}
    if cfg.mlp == "swiglu":
        p["w_gate"] = dense_init(ks[1], (e, d, f), in_axis=1)
        p["w_up"] = dense_init(ks[2], (e, d, f), in_axis=1)
        p["w_down"] = dense_init(ks[3], (e, f, d), in_axis=1)
    else:
        p["w_up"] = dense_init(ks[1], (e, d, f), in_axis=1)
        p["w_down"] = dense_init(ks[2], (e, f, d), in_axis=1)
    return p


def capacity_for(group_size: int, num_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    c = math.ceil(group_size * top_k / num_experts * capacity_factor)
    return max(top_k if group_size == 1 else 4, (c + 3) // 4 * 4)


def _route(logits, top_k: int, capacity: int):
    """logits (G, GS, E) -> dispatch (G,GS,E,C) bool-ish, combine (G,GS,E,C),
    aux metrics. Pure function of router logits: top-k with per-expert
    position assignment, tokens over capacity are dropped (residual path
    carries them)."""
    g, gs, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, top_k)       # (G,GS,K)

    # Normalize the k gates (Mixtral/DBRX convention).
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    dispatch = jnp.zeros((g, gs, e, capacity), jnp.bfloat16)
    combine = jnp.zeros((g, gs, e, capacity), jnp.float32)
    # running token count per (group, expert) across the k rounds
    counts = jnp.zeros((g, e), jnp.int32)
    for kk in range(top_k):
        eh = jax.nn.one_hot(expert_ids[..., kk], e, dtype=jnp.int32)  # (G,GS,E)
        pos = jnp.cumsum(eh, axis=1) - 1 + counts[:, None, :]          # slot idx
        counts = counts + eh.sum(axis=1)
        pos_tok = jnp.take_along_axis(
            pos, expert_ids[..., kk:kk + 1], axis=-1)[..., 0]          # (G,GS)
        keep = pos_tok < capacity
        ph = jax.nn.one_hot(pos_tok, capacity, dtype=jnp.float32)      # (G,GS,C)
        sel = (eh.astype(jnp.float32) * keep[..., None].astype(jnp.float32))
        contrib = sel[..., None] * ph[..., None, :]                    # (G,GS,E,C)
        dispatch = dispatch + contrib.astype(jnp.bfloat16)
        combine = combine + gate_vals[..., kk, None, None] * contrib

    # aux: Switch load-balance loss + router z-loss
    density = dispatch.sum(axis=(1, 3)) / gs                            # (G,E) frac
    mean_prob = probs.mean(axis=1)                                      # (G,E)
    lb_loss = e * jnp.mean(jnp.sum(density.astype(jnp.float32) * mean_prob, axis=-1))
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)))
    dropped = 1.0 - dispatch.astype(jnp.float32).sum() / (g * gs * top_k)
    aux = {"lb_loss": lb_loss, "z_loss": z_loss, "dropped_frac": dropped}
    return dispatch, combine, aux


def moe_apply(params, cfg, x, group_size: int = DEFAULT_GROUP_SIZE):
    """x (B, S, D) -> (out, aux)."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    gs = min(group_size, s) if s > 1 else 1
    gcount = t // gs
    xg = x.reshape(gcount, gs, d)

    logits = xg @ params["w_router"].astype(x.dtype)                    # (G,GS,E)
    cap = capacity_for(gs, m.num_experts, m.top_k, m.capacity_factor)
    dispatch, combine, aux = _route(logits, m.top_k, cap)

    expert_in = jnp.einsum("gsec,gsd->egcd", dispatch.astype(x.dtype), xg)
    if cfg.mlp == "swiglu":
        h = silu(jnp.einsum("egcd,edf->egcf", expert_in,
                            params["w_gate"].astype(x.dtype)))
        h = h * jnp.einsum("egcd,edf->egcf", expert_in,
                           params["w_up"].astype(x.dtype))
    else:
        h = jax.nn.gelu(jnp.einsum("egcd,edf->egcf", expert_in,
                                   params["w_up"].astype(x.dtype)))
    expert_out = jnp.einsum("egcf,efd->egcd", h, params["w_down"].astype(x.dtype))
    out = jnp.einsum("egcd,gsec->gsd", expert_out, combine.astype(x.dtype))
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Dropless routed experts with expert parallelism (DeepSeek-V3 block)
#
# The layer is told which experts it holds (``MoEConfig.first_held`` ..
# ``+ experts_held``), routes every token over all ``num_experts``, and
# computes the part of the result its held experts give, plus the shared
# experts. No token is dropped: every (token, expert) pair that lands on a
# held expert is computed. On one chip the layer runs without the
# exchange a deployment's other chips would add; nothing stands in for
# their experts.

# counters returned by moe_routed_apply, summed over layers and calls
MOE_STATS = ("pairs", "pairs_here", "experts_active")


def moe_routed_init(key, cfg):
    from repro.models.mlp import mlp_init
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff, m.held
    ks = jax.random.split(key, 5)
    p = {"w_router": dense_init(ks[0], (d, m.num_experts)),
         "w_gate": dense_init(ks[1], (e, d, f), in_axis=1),
         "w_up": dense_init(ks[2], (e, d, f), in_axis=1),
         "w_down": dense_init(ks[3], (e, f, d), in_axis=1)}
    if m.expert_bias:
        p["e_bias"] = jnp.zeros((m.num_experts,), jnp.float32)
    if m.n_shared:
        p["shared"] = mlp_init(ks[4], cfg, d_ff=m.n_shared * f)
    return p


def route(params, cfg, x2):
    """x2 (T, D) -> (expert ids (T, k) over all num_experts, gates (T, k)
    f32). Scores softmax or sigmoid of f32 logits; the expert bias (if
    any) enters the selection only; gates are the unbiased scores of the
    chosen experts, normalized over the k if ``norm_topk``, times
    ``routed_scale``."""
    m = cfg.moe
    logits = jnp.dot(x2.astype(jnp.float32),
                     params["w_router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits) if m.score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    sel = scores + params["e_bias"].astype(jnp.float32) \
        if m.expert_bias else scores
    _, ids = jax.lax.top_k(sel, m.top_k)
    gates = jnp.take_along_axis(scores, ids, axis=-1)
    if m.norm_topk:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return ids, gates * m.routed_scale


def _dense_experts(params, x2, combine):
    """Every held expert on every token, weighted by ``combine`` (T, E):
    the dense-weight form (calibration, training, the CPU lane)."""
    dt = x2.dtype
    h = silu(jnp.einsum("td,edf->etf", x2, params["w_gate"].astype(dt)))
    h = h * jnp.einsum("td,edf->etf", x2, params["w_up"].astype(dt))
    y = jnp.einsum("etf,efd->etd", h, params["w_down"].astype(dt),
                   preferred_element_type=jnp.float32)
    return jnp.einsum("te,etd->td", combine, y)


def _tile_rows(tokens: int, k: int, held: int) -> int:
    """Row-tile height of the grouped dispatch: 8 rows (one f32 tile)
    when few pairs can land here, as in a decode step, else 128."""
    return 8 if tokens * min(k, held) <= 64 else 128


def _grouped_experts(params, x2, local, gates, held: int):
    """The held experts' part through the grouped quantized kernel
    (``ops.grouped_qdense``): pairs sorted by expert, each expert's rows
    padded to whole row tiles, only tiles that hold rows computed.
    local (T, k): held-expert index of each pair, ``held`` where the
    expert is not held here."""
    from repro.kernels import ops
    t, k = local.shape
    bm = _tile_rows(t, k, held)
    n_pairs = t * k
    n_g = -(-(t * min(k, held) + held * (bm - 1)) // bm)   # tile bound
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    e_sorted = flat[order]
    counts = jnp.zeros((held + 1,), jnp.int32).at[flat].add(1)[:held]
    padded = (counts + bm - 1) // bm * bm
    starts = jnp.cumsum(padded) - padded                    # padded rows
    first = jnp.cumsum(counts) - counts                     # sorted pairs
    valid = e_sorted < held
    e_c = jnp.minimum(e_sorted, held - 1)
    row = jnp.where(valid, starts[e_c] + jnp.arange(n_pairs) - first[e_c],
                    n_g * bm)
    tok = order // k
    xs = jnp.zeros((n_g * bm, x2.shape[1]), jnp.float32).at[row].set(
        x2[tok].astype(jnp.float32), mode="drop")
    ends = jnp.cumsum(padded)
    n_tiles = ends[-1] // bm
    tile_expert = jnp.minimum(jnp.searchsorted(
        ends, jnp.arange(n_g) * bm, side="right"), held - 1).astype(
            jnp.int32)

    def run():
        g = ops.grouped_qdense(xs, params["w_gate"], tile_expert, n_tiles,
                               bm=bm, bk=512, bn=1536)
        u = ops.grouped_qdense(xs, params["w_up"], tile_expert, n_tiles,
                               bm=bm, bk=512, bn=1536)
        return ops.grouped_qdense(silu(g) * u, params["w_down"],
                                  tile_expert, n_tiles, bm=bm, bk=1536,
                                  bn=512)

    # a call where no pair landed here reads no expert at all
    y = jax.lax.cond(n_tiles > 0, run,
                     lambda: jnp.zeros((n_g * bm, x2.shape[1]), jnp.float32))
    rows = jnp.where(valid[:, None], y[jnp.minimum(row, n_g * bm - 1)], 0.0)
    w = jnp.where(valid, gates.reshape(-1)[order], 0.0)
    return jnp.zeros((t, x2.shape[1]), jnp.float32).at[tok].add(
        rows * w[:, None])


def moe_routed_apply(params, cfg, x):
    """x (B, S, D) -> (out, stats): the dropless routed-expert layer.

    out = sum over the token's top-k experts HELD here of gate * SwiGLU
    expert, plus the shared experts. Expert stacks arriving as
    quantized wire structs run through the grouped kernel; dense stacks
    through every held expert on every token (same sums). ``stats``
    (int32, ``MOE_STATS``): pairs routed (k per token), pairs that
    landed on held experts, and held experts that got at least one."""
    from repro.kernels import ops
    from repro.models.mlp import mlp_apply
    m = cfg.moe
    b, s, d = x.shape
    held = m.held
    x2 = x.reshape(b * s, d)
    ids, gates = route(params, cfg, x2)
    local = ids - m.first_held
    here = (local >= 0) & (local < held)
    onehot = jax.nn.one_hot(jnp.where(here, local, held), held + 1,
                            dtype=jnp.float32)[..., :held]   # (T, k, E)
    if ops.is_wire_struct(params["w_up"]):
        out = _grouped_experts(params, x2, jnp.where(here, local, held),
                               gates, held)
    else:
        out = _dense_experts(params, x2,
                             jnp.einsum("tk,tke->te", gates, onehot))
    out = out.astype(x.dtype).reshape(b, s, d)
    if "shared" in params:
        out = out + mlp_apply(params["shared"], cfg, x)
    stats = jnp.stack([jnp.int32(b * s * m.top_k),
                       here.sum().astype(jnp.int32),
                       (onehot.sum((0, 1)) > 0).sum().astype(jnp.int32)])
    return out, stats
