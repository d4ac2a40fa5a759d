"""Kernel-grade decode (PR 9): the single-query flash-attention decode
kernel against the jnp oracle (shape/dtype/GQA sweep incl. float8 cache
storage), the ``REPRO_KERNELS`` dispatch contract, the quantized dense
contraction ``ops.qdense`` against fake-quant matmuls, and the
quantized-kernel device segment (``qstacked_for`` wire structs through
``segment_decode_step``) matching the dense fake-quant path on the SAME
compile-once program budget across cuts."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.quantizer import fake_quant, quantize_stacked
from repro.core.solver import PartitionPlan
from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.models import transformer as T
from repro.serving.backends import TransformerBackend
from repro.serving.decode import DecodeSession

pytestmark = pytest.mark.smoke

KEY = jax.random.key(0)
SEQ = 16
MAX_LEN = 48

# locked parity tolerances: interpret-mode kernel vs the jnp oracle
TOL_F32 = 2e-6
TOL_BF16 = 2e-2


def _manual_plan(p: int, bits: float = 16.0) -> PartitionPlan:
    return PartitionPlan(p=p, bits_w=np.full(p, float(bits)),
                         bits_x=float(bits), objective=0.0, psi_total=0.0,
                         payload_bits=0.0, breakdown={})


def _qkv(key, b, buf, kvp, gp, hd, dtype, cache_dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, kvp, gp, hd), dtype)
    ck = jax.random.normal(kk, (b, buf, kvp, hd), dtype).astype(cache_dtype)
    cv = jax.random.normal(kv, (b, buf, kvp, hd), dtype).astype(cache_dtype)
    return q, ck, cv


class TestDecodeAttentionKernel:
    """Interpret-mode Pallas kernel == jnp oracle across the layout
    sweep the serving path produces."""

    @pytest.mark.parametrize("b,kvp,gp,buf,hd", [
        (2, 4, 1, 64, 128),      # MHA (group of 1)
        (1, 2, 4, 64, 64),       # GQA
        (2, 1, 8, 128, 64),      # MQA-ish: one KV head, wide group
        (1, 4, 2, 256, 64),      # multi-block ring (nk > 1)
    ])
    def test_parity_shapes(self, b, kvp, gp, buf, hd):
        q, ck, cv = _qkv(KEY, b, buf, kvp, gp, hd, jnp.float32, jnp.float32)
        for pos in (0, 3, buf - 1, buf + 7, 5 * buf + 1):
            want = ref.decode_attention_ref(q, ck, cv, pos)
            got = decode_attention_pallas(q, ck, cv, pos, interpret=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=TOL_F32, rtol=0)

    @pytest.mark.parametrize("cache_dtype,tol", [
        (jnp.bfloat16, TOL_BF16),
        (jnp.float8_e4m3fn, TOL_BF16),
    ], ids=["bf16", "float8"])
    def test_parity_quantized_cache_dtypes(self, cache_dtype, tol):
        """The deployed-bit-width cache storage dtypes (float8 for <= 8
        device bits) go through the kernel's f32 upcast exactly like the
        oracle's."""
        q, ck, cv = _qkv(KEY, 2, 64, 2, 2, 64, jnp.float32, cache_dtype)
        for pos in (5, 63, 100):
            want = ref.decode_attention_ref(q, ck, cv, pos)
            got = decode_attention_pallas(q, ck, cv, pos, interpret=True)
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(want, np.float32),
                atol=tol, rtol=0)

    def test_bf16_query_parity(self):
        q, ck, cv = _qkv(KEY, 1, 64, 2, 2, 64, jnp.bfloat16, jnp.bfloat16)
        want = ref.decode_attention_ref(q, ck, cv, 40)
        got = decode_attention_pallas(q, ck, cv, 40, interpret=True)
        assert got.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=TOL_BF16, rtol=0)

    def test_partial_ring_masks_unwritten_slots(self):
        """pos + 1 < buf: garbage beyond the write head must not leak
        into the softmax (validity mask, not zero-padding)."""
        q, ck, cv = _qkv(KEY, 1, 64, 1, 2, 64, jnp.float32, jnp.float32)
        poisoned_k = ck.at[:, 10:].set(1e4)      # pos=9 -> slots 10+ dead
        poisoned_v = cv.at[:, 10:].set(1e4)
        want = ref.decode_attention_ref(q, ck, cv, 9)
        got = decode_attention_pallas(q, poisoned_k, poisoned_v, 9,
                                      interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=TOL_F32, rtol=0)


class TestKernelModeDispatch:
    def test_auto_is_reference_off_tpu(self, monkeypatch):
        monkeypatch.delenv("REPRO_KERNELS", raising=False)
        expected = "kernel" if jax.default_backend() == "tpu" \
            else "reference"
        assert ops.kernel_mode() == expected

    @pytest.mark.parametrize("mode", ops.KERNEL_MODES[1:])
    def test_explicit_modes(self, mode, monkeypatch):
        monkeypatch.setenv("REPRO_KERNELS", mode)
        assert ops.kernel_mode() == mode

    def test_invalid_mode_raises(self, monkeypatch):
        monkeypatch.setenv("REPRO_KERNELS", "mosaic")
        with pytest.raises(ValueError, match="REPRO_KERNELS"):
            ops.kernel_mode()

    def test_dispatch_routes_to_oracle(self, monkeypatch):
        """reference mode and interpret mode agree through the public
        entry point — the lane flip changes execution, not values."""
        q, ck, cv = _qkv(KEY, 1, 64, 2, 2, 64, jnp.float32, jnp.float32)
        monkeypatch.setenv("REPRO_KERNELS", "reference")
        a = np.asarray(ops.decode_attention(q, ck, cv, 17))
        monkeypatch.setenv("REPRO_KERNELS", "interpret")
        b = np.asarray(ops.decode_attention(q, ck, cv, 17))
        np.testing.assert_allclose(a, b, atol=TOL_F32, rtol=0)


class TestQDense:
    """ops.qdense == x @ dequant(struct) for every wire layout the
    stacked quantizer emits."""

    def _struct_and_dense(self, key, shape, bits, per_channel):
        w = jax.random.normal(key, (1,) + shape, jnp.float32)  # 1 period
        q = quantize_stacked(w, bits, per_channel=per_channel)
        sliced = {k: v[0] for k, v in q.items()}               # period slice
        codes = q["codes"] if "codes" in q else None
        if codes is None:                                      # unpack int4
            codes = ref.unpack_int4_ref(q["codes_packed"], axis=1)
        dense = (codes.astype(jnp.float32) * q["scale"] + q["mu"])[0]
        return sliced, dense

    @pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
    @pytest.mark.parametrize("per_channel", [True, False],
                             ids=["per-channel", "per-tensor"])
    def test_matmul_2d(self, bits, per_channel, monkeypatch):
        monkeypatch.setenv("REPRO_KERNELS", "reference")
        struct, dense = self._struct_and_dense(KEY, (48, 64), bits,
                                               per_channel)
        x = jax.random.normal(KEY, (2, 5, 48), jnp.float32)
        got = ops.qdense(x, struct)
        want = jnp.einsum("bsd,dn->bsn", x, dense)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)

    def test_qkv_projection_3d_out(self, monkeypatch):
        """(D, H, hd) projection: contraction over D, struct output tail
        (H, hd) — per-channel metadata is per-head-dim, broadcast over
        the flattened columns."""
        monkeypatch.setenv("REPRO_KERNELS", "reference")
        struct, dense = self._struct_and_dense(KEY, (64, 4, 32), 8, True)
        x = jax.random.normal(KEY, (2, 5, 64), jnp.float32)
        got = ops.qdense(x, struct)
        want = jnp.einsum("bsd,dhk->bshk", x, dense)
        assert got.shape == (2, 5, 4, 32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)

    def test_out_projection_contracts_two_axes(self, monkeypatch):
        monkeypatch.setenv("REPRO_KERNELS", "reference")
        struct, dense = self._struct_and_dense(KEY, (4, 32, 64), 8, True)
        x = jax.random.normal(KEY, (2, 5, 4, 32), jnp.float32)
        got = ops.qdense(x, struct, n_contract=2)
        want = jnp.einsum("bshk,hkd->bsd", x, dense)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)

    def test_interpret_matches_reference(self, monkeypatch):
        """The Pallas qmatmul lane (interpret) agrees with the jnp lane
        through the same dispatch — both int8 and packed int4."""
        x = jax.random.normal(KEY, (6, 48), jnp.float32)
        for bits in (8, 4):
            struct, _ = self._struct_and_dense(KEY, (48, 64), bits, True)
            monkeypatch.setenv("REPRO_KERNELS", "reference")
            a = np.asarray(ops.qdense(x, struct))
            monkeypatch.setenv("REPRO_KERNELS", "interpret")
            b = np.asarray(ops.qdense(x, struct))
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


class TestQuantizedKernelSegment:
    """``qstacked_for`` wire structs through the compile-once decode
    programs == the dense fake-quant path (``stacked_for``)."""

    @pytest.fixture(scope="class")
    def lm(self):
        cfg = dataclasses.replace(
            get_config("smollm-135m").reduced(), name="smollm-qkern",
            d_model=64, num_heads=2, num_kv_heads=1, head_dim=32, d_ff=128,
            vocab_size=32, tp_pad=1, dtype="float32")
        return cfg, T.init_params(KEY, cfg)

    @pytest.mark.parametrize("bits", [8.0, 4.0], ids=["int8", "int4pack"])
    def test_tokens_match_dense_fake_quant_path(self, lm, bits):
        cfg, params = lm
        backend = TransformerBackend(cfg, params, seq_len=SEQ)
        prompt = jax.random.randint(KEY, (2, SEQ), 0, cfg.vocab_size)
        p = cfg.num_layers
        dense = DecodeSession(backend, _manual_plan(p, bits=bits),
                              max_len=MAX_LEN, qkernels=False)
        qkern = DecodeSession(backend, _manual_plan(p, bits=bits),
                              max_len=MAX_LEN, qkernels=True)
        r0 = dense.generate(prompt, 6)
        r1 = qkern.generate(prompt, 6)
        np.testing.assert_array_equal(r1.tokens, r0.tokens)

    def test_struct_dequant_matches_split_blocks(self, lm):
        """dequant(qstacked codes) on the active periods == the
        fake-quant leaves ``split_blocks`` ships — bit for bit, the
        invariant that makes token parity exact rather than approximate."""
        cfg, params = lm
        backend = TransformerBackend(cfg, params, seq_len=SEQ)
        plan = _manual_plan(cfg.num_layers, bits=6.0)
        seg = backend.split(plan)
        qtree = backend.qstacked_for(seg, plan)
        dtree = backend.stacked_for(seg, plan)
        plen = T.period_len(cfg)
        for pos in range(plen):
            for name, keys in T.KERNEL_ROUTED.items():
                if name not in qtree["blocks"][pos]:
                    continue
                for k in keys:
                    if k not in qtree["blocks"][pos][name]:
                        continue
                    s = qtree["blocks"][pos][name][k]
                    codes = s["codes"].astype(jnp.float32)
                    deq = codes * s["scale"] + s["mu"]
                    np.testing.assert_array_equal(
                        np.asarray(deq),
                        np.asarray(dtree["blocks"][pos][name][k]))

    def test_compile_once_across_cuts(self, lm):
        """The struct tree keys its own programs, but the pytree
        structure is cut-independent: after the first quantized-kernel
        cut, further cuts at the same bit-widths add ZERO traces."""
        cfg, params = lm
        backend = TransformerBackend(cfg, params, seq_len=SEQ)
        prompt = jax.random.randint(KEY, (2, SEQ), 0, cfg.vocab_size)
        L = cfg.num_layers
        DecodeSession(backend, _manual_plan(1, bits=8.0), max_len=MAX_LEN,
                      qkernels=True).generate(prompt, 4)
        traces = backend.trace_count
        for p in (L // 2, L):
            DecodeSession(backend, _manual_plan(p, bits=8.0),
                          max_len=MAX_LEN, qkernels=True).generate(prompt, 4)
        assert backend.trace_count == traces, \
            "quantized-kernel decode re-traced across cut points"

    def test_moe_expert_stacks_stay_dense(self):
        """The context-sensitive routing must NOT struct-ify MoE expert
        stacks (same key names as MLP weights, different contraction) —
        a qkernels session on an MoE arch still decodes correctly."""
        cfg = dataclasses.replace(
            get_config("olmoe-1b-7b").reduced(), name="moe-qkern",
            vocab_size=32, dtype="float32")
        params = T.init_params(KEY, cfg)
        backend = TransformerBackend(cfg, params, seq_len=8)
        prompt = jax.random.randint(KEY, (1, 8), 0, cfg.vocab_size)
        p = cfg.num_layers
        dense = DecodeSession(backend, _manual_plan(p, bits=8.0),
                              max_len=24, qkernels=False)
        qkern = DecodeSession(backend, _manual_plan(p, bits=8.0),
                              max_len=24, qkernels=True)
        np.testing.assert_array_equal(qkern.generate(prompt, 4).tokens,
                                      dense.generate(prompt, 4).tokens)
