"""Sharding rules: parameter / optimizer / cache / batch PartitionSpecs.

Layout (DESIGN.md §5) — activations are sharded over ``data`` on batch and
replicated over ``model``; weights follow Megatron column->row TP pairs so
each block needs exactly one psum on its output projection:

  embed          (V, D)            -> (model, None)        vocab-sharded
  lm_head        (D, V)            -> (None, model)        logits vocab-sharded
  attn wq/wk/wv  (P, D, H*hd)      -> (None, None, model)  head-sharded
  attn wo        (P, H*hd, D)      -> (None, model, None)  row-parallel psum
  mlp  gate/up   (P, D, F)         -> (None, None, model)
  mlp  down      (P, F, D)         -> (None, model, None)
  moe  experts   (P, E, D, F)      -> (None, model, None, None)  expert-parallel
  ssm  w_z/w_x   (P, D, di)        -> (None, None, model)  head-sharded
  ssm  w_out     (P, di, D)        -> (None, model, None)
  ssm  B/C/dt    small, shared across heads -> replicated
  norms / scalars                  -> replicated

``P`` is the stacked num_periods axis (scan over depth), never sharded.
Optimizer mu/nu mirror the parameter specs; ZeRO-style sharding of the
optimizer over ``data`` is a §Perf hillclimb (see fsdp=True).
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.launch.mesh import DATA_AXIS, MODEL_AXIS, POD_AXIS


def data_axes(mesh) -> tuple:
    """Axes that carry the global batch (pod included when present)."""
    if POD_AXIS in mesh.axis_names:
        return (POD_AXIS, DATA_AXIS)
    return (DATA_AXIS,)


def _leaf_spec(path, leaf, kv_sharded: bool) -> P:
    keys = [k.key for k in path if hasattr(k, "key")]
    name = keys[-1]
    ndim = leaf.ndim
    M = MODEL_AXIS
    in_block = "blocks" in keys
    # int8 serving weights: {codes, scale, mu} under the weight's name —
    # codes shard like the weight itself; per-period scale/mu replicate
    if name in ("codes", "codes_packed"):   # int4 packing halves the
        name = keys[-2]                     # first non-period dim
    elif name in ("scale", "mu") and len(keys) >= 2 and keys[-2] != name:
        from repro.core.quantizer import QUANTIZABLE
        if keys[-2] in QUANTIZABLE:
            return P(*([None] * ndim))

    def stacked(*spec):
        """Params under blocks/ carry the leading num_periods axis."""
        return P(None, *spec) if in_block else P(*spec)

    if name == "embed":
        return P(M, None)
    if name == "lm_head":
        return P(None, M)
    if name in ("scale", "bias"):                 # norms
        return stacked(None)
    # attention (flat padded-head layout, DESIGN.md §5) -------------------
    if name == "wq":                              # (D, H_pad, hd)
        return stacked(None, M, None)
    if name == "wo":                              # (H_pad, hd, D)
        return stacked(M, None, None)
    if name == "bq":                              # (H_pad, hd)
        return stacked(M, None)
    if name in ("wk", "wv"):                      # (D, KV_pad, hd)
        return stacked(None, M, None) if kv_sharded else \
            stacked(None, None, None)
    if name in ("bk", "bv"):                      # (KV_pad, hd)
        return stacked(M, None) if kv_sharded else stacked(None, None)
    if name in ("q_norm", "k_norm"):
        return stacked(None)
    # moe / mlp ---------------------------------------------------------
    if name == "w_router":
        return stacked(None, None)
    if name in ("w_gate", "w_up"):
        if ndim == 4:                             # (P, E, D, F) expert-parallel
            return stacked(M, None, None)
        return stacked(None, M)                   # dense mlp (P, D, F)
    if name == "w_down":
        if ndim == 4:                             # (P, E, F, D)
            return stacked(M, None, None)
        return stacked(M, None)                   # dense mlp (P, F, D)
    # ssm ----------------------------------------------------------------
    if name in ("w_z", "w_x"):
        return stacked(None, M)
    if name in ("w_B", "w_C", "w_dt"):
        return stacked(None, None)
    if name == "conv_wx":
        return stacked(None, M)
    if name == "conv_bx":
        return stacked(M)
    if name in ("conv_wB", "conv_wC"):
        return stacked(None, None)
    if name in ("conv_bB", "conv_bC"):
        return stacked(None)
    if name in ("dt_bias", "A_log", "D"):
        return stacked(None)
    if name == "gate_norm":
        return stacked(M)
    if name == "w_out":
        return stacked(M, None)
    raise ValueError(f"no sharding rule for param {'/'.join(map(str, keys))} "
                     f"with ndim={ndim}")


def _with_fsdp(spec: P, leaf, mesh) -> P:
    """ZeRO-3 flavour: additionally shard the largest unsharded dim over
    ``data`` when it divides evenly (hillclimb candidate, DESIGN.md §5)."""
    ndim = leaf.ndim
    dsize = int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
    parts = list(spec) + [None] * (ndim - len(spec))
    # pick the largest dim not already sharded
    cand = [(leaf.shape[i], i) for i in range(ndim) if parts[i] is None]
    for size, i in sorted(cand, reverse=True):
        if size % dsize == 0 and size >= dsize:
            parts[i] = data_axes(mesh) if len(data_axes(mesh)) > 1 else DATA_AXIS
            break
    return P(*parts)


def param_pspecs(cfg: ModelConfig, params_shape, *, fsdp: bool = False,
                 mesh=None) -> Any:
    """PartitionSpec tree matching ``transformer.init_params`` output."""
    msize = mesh.shape[MODEL_AXIS] if mesh is not None else 16
    kv_sharded = bool(cfg.num_heads) and cfg.padded_heads()[0] % msize == 0

    def rule(path, leaf):
        spec = _leaf_spec(path, leaf, kv_sharded)
        if getattr(path[-1], "key", None) == "codes_packed" and \
                len(spec) > 1 and spec[1] == MODEL_AXIS and \
                leaf.shape[1] % msize:
            # int4 packing halved a model-sharded dim below a multiple of
            # the mesh: that dim replicates
            spec = P(spec[0], None, *spec[2:])
        if fsdp:
            assert mesh is not None
            spec = _with_fsdp(spec, leaf, mesh)
        return spec

    return jax.tree_util.tree_map_with_path(rule, params_shape)


def opt_pspecs(param_specs) -> Any:
    """mu / nu mirror the params; the step counter is replicated."""
    return {"mu": param_specs, "nu": param_specs, "step": P()}


def cache_pspecs(cfg: ModelConfig, cache_shape, mesh, batch: int) -> Any:
    """KV / SSM cache specs. Leaves (stacked over periods):
      attn k/v   (P, B, buf, KV, hd) -> (None, data, None, model?, None)
                 (KV sharded only when attn_shard_dim == 'kv'; when the
                  G dim carries TP the small KV cache replicates)
      ssm state  (P, B, H, N, hd)    -> (None, data, model, None, None)
      ssm conv   (P, B, W-1, C)      -> (None, data, None, None)   (packed)
    Batch replicates when it cannot split over data (long_500k B=1)."""
    daxes = data_axes(mesh)
    dsize = int(np.prod([mesh.shape[a] for a in daxes]))
    b_ax = daxes if batch % dsize == 0 and batch >= dsize else None
    b_ax = b_ax if b_ax is None or len(daxes) > 1 else DATA_AXIS
    kv_ax = None
    if cfg.num_heads and cfg.padded_heads()[0] % mesh.shape[MODEL_AXIS] == 0:
        kv_ax = MODEL_AXIS

    def rule(path, leaf):
        keys = [k.key for k in path if hasattr(k, "key")]
        name = keys[-1]
        if name in ("k", "v"):
            if kv_ax is None and leaf.shape[2] % mesh.shape[MODEL_AXIS] == 0:
                # GQA kv-heads don't divide the model axis: shard the
                # SEQUENCE (ring-buffer) dim instead of replicating — the
                # cache dominates decode memory (42.5 GiB/device replicated
                # for qwen3 decode_32k; §Perf pair C). The flash-decode
                # softmax runs distributed over sequence shards (psum of
                # max/sum stats), a tiny collective vs a 16x cache read.
                return P(None, b_ax, MODEL_AXIS, None, None)
            return P(None, b_ax, None, kv_ax, None)
        if name == "state":
            return P(None, b_ax, MODEL_AXIS, None, None)
        if name == "conv":
            return P(None, b_ax, None, None)
        raise ValueError(f"no cache rule for {keys}")

    return jax.tree_util.tree_map_with_path(rule, cache_shape)


def batch_pspecs(mesh, batch: int, has_embeds: bool, has_positions: bool) -> dict:
    daxes = data_axes(mesh)
    dsize = int(np.prod([mesh.shape[a] for a in daxes]))
    b_ax = daxes if batch % dsize == 0 and batch >= dsize else None
    b_ax = b_ax if b_ax is None or len(daxes) > 1 else DATA_AXIS
    specs = {"labels": P(b_ax, None)}
    if has_embeds:
        specs["embeds"] = P(b_ax, None, None)
    else:
        specs["tokens"] = P(b_ax, None)
    if has_positions:
        specs["positions"] = P(None, b_ax, None)
    return specs


def shardings_of(mesh, pspecs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))
