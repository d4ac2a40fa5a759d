"""Pallas kernel validation: shape/dtype sweeps, interpret=True vs the
pure-jnp oracles in ref.py (per-kernel allclose, deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantizer import quantize
from repro.kernels import ops, ref
from repro.kernels.qmatmul import qmatmul4_pallas, qmatmul_pallas
from repro.kernels.quantize import (dequantize_pallas, quantize_pack4_pallas,
                                    quantize_pallas)

KEY = jax.random.key(0)

SHAPES = [(128, 128), (256, 512), (512, 256), (1024, 1024)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _w(shape, dtype=jnp.float32, seed=0):
    return jax.random.normal(jax.random.key(seed), shape, dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("bits", [4, 8])
class TestQuantizeKernel:
    def test_quantize_matches_ref(self, shape, bits):
        x = _w(shape)
        codes, scale, mu = quantize(x, bits)
        k = quantize_pallas(x, scale, mu, bits, interpret=True)
        r = ref.quantize_ref(x, scale, mu, bits)
        # round-to-nearest ties can differ by 1 ulp across impls; demand
        # exactness here since both use jnp.round
        np.testing.assert_array_equal(np.asarray(k), np.asarray(r))

    def test_dequantize_matches_ref(self, shape, bits):
        x = _w(shape)
        codes, scale, mu = quantize(x, bits)
        codes8 = codes.astype(jnp.uint8)
        k = dequantize_pallas(codes8, scale, mu, jnp.float32, interpret=True)
        r = ref.dequantize_ref(codes8, scale, mu, jnp.float32)
        np.testing.assert_allclose(np.asarray(k), np.asarray(r),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mkn", [(128, 128, 128), (256, 512, 256),
                                 (64, 1024, 128), (512, 256, 512)])
@pytest.mark.parametrize("xdtype", DTYPES)
class TestQMatmulKernel:
    def test_w8_matches_ref(self, mkn, xdtype):
        m, k, n = mkn
        x = _w((m, k), xdtype)
        codes, scale, mu = quantize(_w((k, n), seed=1), 8)
        codes8 = codes.astype(jnp.uint8)
        out_k = qmatmul_pallas(x, codes8, scale, mu, jnp.float32,
                               interpret=True)
        out_r = ref.qmatmul_ref(x, codes8, scale, mu, jnp.float32)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=2e-2 if xdtype == jnp.bfloat16 else 1e-4,
                                   atol=1e-2)

    def test_w4_matches_ref(self, mkn, xdtype):
        m, k, n = mkn
        x = _w((m, k), xdtype)
        codes, scale, mu = quantize(_w((k, n), seed=2), 4)
        packed = ref.pack_int4_ref(codes)
        out_k = qmatmul4_pallas(x, packed, scale, mu, jnp.float32,
                                interpret=True)
        out_r = ref.qmatmul4_ref(x, packed, scale, mu, jnp.float32)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=2e-2 if xdtype == jnp.bfloat16 else 1e-4,
                                   atol=1e-2)


def _per_channel_qparams(w, bits):
    """Per-output-column asymmetric grid (Eq. 9–10 at channel granularity)."""
    mu = jnp.min(w, axis=0, keepdims=True)
    phi = jnp.max(w, axis=0, keepdims=True)
    levels = (1 << bits) - 1
    scale = jnp.maximum((phi - mu) / levels, 1e-12)
    codes = jnp.clip(jnp.round((w - mu) / scale), 0, levels)
    return codes, scale, mu


@pytest.mark.parametrize("mkn", [(128, 128, 128), (256, 512, 256),
                                 (64, 1024, 128)])
class TestPerChannelQMatmul:
    """Per-output-column scale/zero blocks streamed through VMEM: the
    kernels must match the jnp oracle and consume quantize_stacked's
    per-period metadata without reformatting (DESIGN.md §4)."""

    def test_w8_per_channel_matches_oracle(self, mkn):
        m, k, n = mkn
        x = _w((m, k))
        codes, scale, mu = _per_channel_qparams(_w((k, n), seed=11), 8)
        codes8 = codes.astype(jnp.uint8)
        out_k = qmatmul_pallas(x, codes8, scale, mu, jnp.float32,
                               interpret=True)
        out_r = ref.qmatmul_ref(x, codes8, scale, mu, jnp.float32)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=1e-5, atol=5e-4)

    def test_w4_per_channel_matches_oracle(self, mkn):
        m, k, n = mkn
        x = _w((m, k))
        codes, scale, mu = _per_channel_qparams(_w((k, n), seed=12), 4)
        packed = ref.pack_int4_ref(codes)
        out_k = qmatmul4_pallas(x, packed, scale, mu, jnp.float32,
                                interpret=True)
        out_r = ref.qmatmul4_ref(x, packed, scale, mu, jnp.float32)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=1e-5, atol=5e-4)


class TestQuantizeStackedToKernel:
    """The serving wire format (core.quantizer.quantize_stacked) plugs
    straight into the kernels: a period slice of codes/scale/mu is a
    valid argument triple."""

    def test_int8_period_slice(self):
        from repro.core.quantizer import quantize_stacked
        x = _w((128, 512))
        w3 = _w((3, 512, 256), seed=13)
        q = quantize_stacked(w3, 8)
        assert q["scale"].shape == (3, 1, 256)          # per-period+channel
        for i in (0, 2):
            out_k = qmatmul_pallas(x, q["codes"][i], q["scale"][i],
                                   q["mu"][i], jnp.float32, interpret=True)
            out_r = ref.qmatmul_ref(x, q["codes"][i], q["scale"][i],
                                    q["mu"][i], jnp.float32)
            np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                       rtol=1e-5, atol=5e-4)

    def test_int4_period_slice(self):
        from repro.core.quantizer import quantize_stacked
        x = _w((128, 512))
        w3 = _w((2, 512, 256), seed=14)
        q = quantize_stacked(w3, 4)
        out_k = qmatmul4_pallas(x, q["codes_packed"][1], q["scale"][1],
                                q["mu"][1], jnp.float32, interpret=True)
        out_r = ref.qmatmul4_ref(x, q["codes_packed"][1], q["scale"][1],
                                 q["mu"][1], jnp.float32)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=1e-5, atol=5e-4)

    def test_per_tensor_metadata_still_accepted(self):
        from repro.core.quantizer import quantize_stacked
        x = _w((128, 256))
        w3 = _w((2, 256, 128), seed=15)
        q = quantize_stacked(w3, 8, per_channel=False)
        assert q["scale"].shape == (2, 1, 1)
        out_k = qmatmul_pallas(x, q["codes"][0], q["scale"][0], q["mu"][0],
                               jnp.float32, interpret=True)
        out_r = ref.qmatmul_ref(x, q["codes"][0], q["scale"][0], q["mu"][0],
                                jnp.float32)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=1e-5, atol=5e-4)


class TestFusedQuantizePack:
    """quantize_pack4_pallas = Eq. 10 + nibble packing in one VMEM pass;
    must equal quantize_stacked's wire bytes and the jnp oracle."""

    def test_matches_quantize_stacked_and_ref(self):
        from repro.core.quantizer import quantize_stacked
        leaf = _w((2, 128, 256), seed=16)
        q = quantize_stacked(leaf, 4)                    # jnp path (cpu)
        for i in range(2):
            fused = quantize_pack4_pallas(leaf[i], q["scale"][i], q["mu"][i],
                                          interpret=True)
            np.testing.assert_array_equal(np.asarray(fused),
                                          np.asarray(q["codes_packed"][i]))
            np.testing.assert_array_equal(
                np.asarray(fused),
                np.asarray(ref.quantize_pack4_ref(leaf[i], q["scale"][i],
                                                  q["mu"][i])))

    def test_per_tensor_scale(self):
        x = _w((128, 128), seed=17)
        codes, scale, mu = quantize(x, 4)
        fused = quantize_pack4_pallas(x, scale, mu, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(fused), np.asarray(ref.quantize_pack4_ref(x, scale, mu)))

    def test_quantize_stacked_pallas_path_agrees(self):
        from repro.core.quantizer import quantize_stacked
        leaf = _w((3, 256, 512), seed=18)
        jnp_path = quantize_stacked(leaf, 4, use_pallas=False)
        pallas_path = quantize_stacked(leaf, 4, use_pallas=True)
        np.testing.assert_array_equal(np.asarray(pallas_path["codes_packed"]),
                                      np.asarray(jnp_path["codes_packed"]))

    def test_wrapper_dispatch(self):
        x = _w((128, 256), seed=19)
        codes, scale, mu = quantize(x, 4)
        a = ops.quantize_pack4(x, scale, mu, use_pallas=True)
        b = ops.quantize_pack4(x, scale, mu, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestPerChannelQuantizeKernels:
    def test_quantize_dequantize_per_channel(self):
        w = _w((256, 512), seed=20)
        codes, scale, mu = _per_channel_qparams(w, 8)
        codes8 = codes.astype(jnp.uint8)
        k = quantize_pallas(w, scale, mu, 8, interpret=True)
        np.testing.assert_array_equal(np.asarray(k),
                                      np.asarray(ref.quantize_ref(w, scale,
                                                                  mu, 8)))
        d = dequantize_pallas(codes8, scale, mu, jnp.float32, interpret=True)
        np.testing.assert_allclose(
            np.asarray(d),
            np.asarray(ref.dequantize_ref(codes8, scale, mu, jnp.float32)),
            rtol=1e-5, atol=1e-6)


class TestPacking:
    def test_pack_unpack_roundtrip(self):
        codes = jax.random.randint(KEY, (64, 128), 0, 16)
        packed = ref.pack_int4_ref(codes)
        assert packed.shape == (32, 128)          # row halves share a byte
        un = ref.unpack_int4_ref(packed)
        np.testing.assert_array_equal(np.asarray(un), np.asarray(codes))


class TestOpsWrappers:
    """The jit'd public wrappers dispatch pallas-vs-ref equivalently."""

    def test_qmatmul_wrapper_both_paths_agree(self):
        x = _w((256, 512))
        codes, scale, mu = quantize(_w((512, 256), seed=3), 8)
        codes8 = codes.astype(jnp.uint8)
        a = ops.qmatmul(x, codes8, scale, mu, out_dtype=jnp.float32,
                        use_pallas=True)
        b = ops.qmatmul(x, codes8, scale, mu, out_dtype=jnp.float32,
                        use_pallas=False)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)

    def test_block_boundary_shapes(self):
        """Shapes exactly at / above one default block."""
        for m, k, n in [(256, 512, 256), (512, 1024, 512)]:
            x = _w((m, k))
            codes, scale, mu = quantize(_w((k, n), seed=4), 8)
            out = qmatmul_pallas(x, codes.astype(jnp.uint8), scale, mu,
                                 jnp.float32, interpret=True)
            assert out.shape == (m, n)

    def test_quantized_error_shrinks_with_bits(self):
        """End-to-end: W8 matmul error < W4 matmul error (noise law at the
        kernel level)."""
        x = _w((128, 256))
        w = _w((256, 128), seed=5)
        exact = x @ w
        c8, s8, m8 = quantize(w, 8)
        c4, s4, m4 = quantize(w, 4)
        e8 = float(jnp.mean(jnp.abs(
            ref.qmatmul_ref(x, c8, s8, m8, jnp.float32) - exact)))
        e4 = float(jnp.mean(jnp.abs(
            ref.qmatmul4_ref(x, ref.pack_int4_ref(c4), s4, m4, jnp.float32)
            - exact)))
        assert e8 < e4


class TestFlashAttentionKernel:
    """Pallas causal flash attention vs the blocked-attention oracle:
    shape/dtype sweep, exactness of the causal-block skip, GQA index map."""

    @pytest.mark.parametrize("cfg", [
        (2, 256, 2, 2, 64, 128, 128),    # GQA, two kv groups
        (1, 512, 4, 1, 128, 256, 128),   # MQA-ish, hd 128, asym blocks
        (2, 128, 1, 3, 64, 64, 64),      # single kv group, 3 q heads
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_blocked_oracle(self, cfg, dtype):
        from repro.kernels.flash_attention import flash_attention
        from repro.models.attention import _blocked_causal_attention
        b, s, kv, g, hd, bq, bk = cfg
        q = _w((b, s, kv, g, hd), dtype, seed=1)
        k = _w((b, s, kv, hd), dtype, seed=2)
        v = _w((b, s, kv, hd), dtype, seed=3)
        out_k = flash_attention(q, k, v, block_q=bq, block_k=bk,
                                interpret=True)
        out_r = _blocked_causal_attention(q, k, v, bq, bk)
        np.testing.assert_allclose(
            np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
            atol=5e-2 if dtype == jnp.bfloat16 else 1e-5,
            rtol=5e-2 if dtype == jnp.bfloat16 else 1e-5)

    def test_causality(self):
        """Changing a future token never changes an earlier output row."""
        from repro.kernels.flash_attention import flash_attention
        b, s, kv, g, hd = 1, 128, 1, 1, 64
        q = _w((b, s, kv, g, hd), seed=4)
        k = _w((b, s, kv, hd), seed=5)
        v = _w((b, s, kv, hd), seed=6)
        out1 = flash_attention(q, k, v, block_q=64, block_k=64,
                               interpret=True)
        k2 = k.at[:, -1].add(10.0)
        v2 = v.at[:, -1].add(10.0)
        out2 = flash_attention(q, k2, v2, block_q=64, block_k=64,
                               interpret=True)
        np.testing.assert_allclose(np.asarray(out1[:, :-1]),
                                   np.asarray(out2[:, :-1]), atol=1e-6)
        assert float(jnp.max(jnp.abs(out1[:, -1] - out2[:, -1]))) > 1e-3
