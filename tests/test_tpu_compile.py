"""Compiles of the served path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel (or a whole decode-step program)
for one chip of a ``v5e:2x2`` topology that is described, not attached,
and compiles it — Mosaic refuses here what it would refuse on the chip
(unsupported casts, block shapes off the (8, 128) tiling, VMEM
overflow). Shapes are SmolLM-135M's published widths (d_model 576,
d_ff 1536, 9/3 heads padded to 4x4, head_dim 64).

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU compiler library, and the
worker given this file is the one that does.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention
from repro.models import transformer as T
from repro.serving.backends import TransformerBackend

D, F = 576, 1536          # SmolLM-135M d_model, d_ff
KVP, GP, HD = 4, 4, 64    # padded kv heads, padded group, head_dim
B = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield topo
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _on_chip(one_chip, tree):
    return jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype), tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("kn", [(D, F), (F, D)], ids=["K576-N1536",
                                                     "K1536-N576"])
@pytest.mark.parametrize("m", [B, B * 256, B * 37],
                         ids=["decode", "prefill256", "prefill37"])
@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
@pytest.mark.parametrize("per_column", [False, True],
                         ids=["per-tensor", "per-column"])
def test_qdense_compiles(one_chip, monkeypatch, kn, m, bits, per_column):
    """``ops.qdense`` picks the blocks and calls qmatmul/qmatmul4."""
    monkeypatch.setenv("REPRO_KERNELS", "kernel")
    k, n = kn
    meta = (1, n) if per_column else (1, 1)
    codes = ("codes_packed", (k // 2, n)) if bits <= 4 else ("codes", (k, n))
    w = {codes[0]: _shape(one_chip, codes[1], jnp.uint8),
         "scale": _shape(one_chip, meta, jnp.float32),
         "mu": _shape(one_chip, meta, jnp.float32)}
    x = _shape(one_chip, (m, k), jnp.bfloat16)
    _compile(lambda x, w: ops.qdense(x, w), x, w)


@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.float8_e4m3fn],
                         ids=["bf16", "f8"])
def test_decode_attention_compiles(one_chip, cache_dtype):
    q = _shape(one_chip, (B, KVP, GP, HD), jnp.bfloat16)
    ck = _shape(one_chip, (B, 2048, KVP, HD), cache_dtype)
    pos = _shape(one_chip, (), jnp.int32)
    _compile(lambda q, ck, cv, pos: decode_attention_pallas(q, ck, cv, pos),
             q, ck, ck, pos)


def test_flash_attention_compiles(one_chip):
    q = _shape(one_chip, (B, 512, KVP, GP, HD), jnp.bfloat16)
    kv = _shape(one_chip, (B, 512, KVP, HD), jnp.bfloat16)
    _compile(lambda q, k, v: flash_attention(q, k, v), q, kv, kv)


@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
def test_segment_decode_step_compiles(one_chip, monkeypatch, bits):
    """One full-width decode step of the device segment over the
    ``qstacked_for`` wire structs, every block routed to the kernels."""
    monkeypatch.setenv("REPRO_KERNELS", "kernel")
    cfg = get_config("smollm-135m")
    L = cfg.num_layers
    params = T.param_shapes(cfg)
    dev = jax.eval_shape(
        lambda p: TransformerBackend(cfg, p, seq_len=256)._build_qstacked(
            L, [bits] * L), params)
    wq = dev["blocks"][0]["attn"]["wq"]
    assert ops.is_wire_struct(wq)
    assert ("codes_packed" in wq) == (bits <= 4)
    caches = jax.eval_shape(
        lambda: T.init_cache(cfg, B, 2048, jnp.float8_e4m3fn))
    x = _shape(one_chip, (B, 1, cfg.d_model), getattr(jnp, cfg.dtype))
    scalar = _shape(one_chip, (), jnp.int32)

    def step(params, x, caches, pos, start, stop):
        return T.segment_decode_step(params, cfg, x, caches, pos, start,
                                     stop)

    compiled = _compile(step, _on_chip(one_chip, dev), x,
                        _on_chip(one_chip, caches), scalar, scalar, scalar)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 16e9


@pytest.mark.parametrize("kn", [(2048, 1408, 512, 1536),
                                (1408, 2048, 1536, 512)],
                         ids=["gate-up", "down"])
@pytest.mark.parametrize("bm", [8, 128], ids=["decode", "prefill"])
@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
def test_grouped_qdense_compiles(one_chip, monkeypatch, kn, bm, bits):
    """The grouped expert kernel (``moe_gmm``) at Moonlight-16B-A3B's
    expert widths (hidden 2048, expert 1408), 8 held experts, with the
    blocks ``models.moe`` asks for."""
    monkeypatch.setenv("REPRO_KERNELS", "kernel")
    k, n, bk, bn = kn
    packed = bits <= 4
    w = {("codes_packed" if packed else "codes"):
         _shape(one_chip, (8, k // 2 if packed else k, n), jnp.uint8),
         "scale": _shape(one_chip, (1, 1, 1), jnp.float32),
         "mu": _shape(one_chip, (1, 1, 1), jnp.float32)}
    g = 8 if bm == 8 else 32
    compiled = _compile(
        lambda x, w, te, nt: ops.grouped_qdense(x, w, te, nt, bm=bm, bk=bk,
                                                bn=bn),
        _shape(one_chip, (g * bm, k), jnp.float32), w,
        _shape(one_chip, (g,), jnp.int32), _shape(one_chip, (), jnp.int32))
    assert "moe_gmm" in compiled.as_text()


@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
def test_moonlight_decode_step_compiles(one_chip, monkeypatch, bits):
    """One decode step of Moonlight-16B-A3B's device segment at its cut
    (a dense layer and 4 expert layers of 8 held experts, published
    widths) over the wire structs, the routed layer's counters carried:
    the expert stacks reach the grouped kernel as codes, so the step's
    temporaries hold no dense copy of them (one layer's 8 experts are
    277 MB in float32)."""
    monkeypatch.setenv("REPRO_KERNELS", "kernel")
    base = get_config("moonlight-16b-a3b")
    cfg = dataclasses.replace(base, num_layers=5, vocab_size=20_480,
                              moe=dataclasses.replace(base.moe,
                                                      experts_held=8))
    L = cfg.num_layers
    dev = jax.eval_shape(
        lambda p: TransformerBackend(cfg, p, seq_len=256)._build_qstacked(
            L, [bits] * L), T.param_shapes(cfg))
    w = dev["blocks"][1]["moe"]["w_up"]
    assert ("codes_packed" in w) == (bits <= 4)
    caches = jax.eval_shape(
        lambda: T.init_cache(cfg, 1, 2048, jnp.float8_e4m3fn))
    scalar = _shape(one_chip, (), jnp.int32)

    def step(params, x, caches, pos, start, stop, stats):
        return T.segment_decode_step(params, cfg, x, caches, pos, start,
                                     stop, stats)

    compiled = _compile(step, _on_chip(one_chip, dev),
                        _shape(one_chip, (1, 1, cfg.d_model), jnp.bfloat16),
                        _on_chip(one_chip, caches), scalar, scalar, scalar,
                        _shape(one_chip, (L, 3), jnp.int32))
    assert "moe_gmm" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6


def test_tile_choice_is_mosaic_legal():
    """Block dims are multiples of the tiling or the whole dim."""
    for dim in (8, 296, 576, 768, 1536, 2048, 49152):
        for pref, align in ((256, 8), (512, 128), (256, 128)):
            t = ops._tile(dim, pref, align)
            assert dim % t == 0
            assert t == dim or (t % align == 0 and t <= pref)
    assert ops._tile(576, 512) == 576 and ops._tile(576, 256) == 576
    assert ops._tile(B * 37, 256, align=8) == 8
