"""Quantizer unit + property tests (paper Eq. 9–10, 18–19)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.core.quantizer import (analytic_noise_scale, dequantize,
                                  fake_quant, payload_bits, quant_noise_energy,
                                  quantize, round_bits)

LN4 = np.log(4.0)

pytestmark = pytest.mark.smoke


def _rand(shape, seed=0, lo=-3.0, hi=5.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(lo, hi, size=shape).astype(np.float32))


class TestQuantizeBasics:
    def test_codes_in_range(self):
        x = _rand((64, 32))
        for bits in (2, 4, 8, 12):
            codes, scale, mu = quantize(x, bits)
            assert int(codes.min()) >= 0
            assert int(codes.max()) <= (1 << bits) - 1

    def test_roundtrip_error_bounded_by_half_step(self):
        x = _rand((128,))
        for bits in (3, 5, 8):
            codes, scale, mu = quantize(x, bits)
            xq = dequantize(codes, scale, mu)
            assert float(jnp.max(jnp.abs(x - xq))) <= float(scale) / 2 + 1e-6

    def test_extremes_are_exact_gridpoints(self):
        x = _rand((50,))
        codes, scale, mu = quantize(x, 8)
        xq = dequantize(codes, scale, mu)
        assert np.isclose(float(xq.min()), float(x.min()), atol=1e-5)
        assert np.isclose(float(xq.max()), float(x.max()), atol=1e-5)

    def test_fake_quant_idempotent(self):
        x = _rand((32, 16))
        q1 = fake_quant(x, 6)
        q2 = fake_quant(q1, 6)
        np.testing.assert_allclose(np.asarray(q1), np.asarray(q2), atol=1e-5)

    def test_round_bits_clips(self):
        b = jnp.array([0.3, 2.2, 7.9, 40.0])
        r = np.asarray(round_bits(b, lo=2, hi=16))
        assert r.tolist() == [2, 3, 8, 16]

    def test_round_bits_host_matches_device(self):
        """Rounded on the host to the integers the jnp ceil/clip gives,
        including values a hair above an integer that float32 drops."""
        b = np.array([0.3, 2.2, 5.0 + 1e-9, 6.0000001, 7.9, 40.0])
        host = round_bits(b)
        device = jnp.clip(jnp.ceil(jnp.asarray(b)), 2, 16).astype(jnp.int32)
        assert isinstance(host, np.ndarray) and host.dtype == np.int32
        assert host.tolist() == np.asarray(device).tolist() == \
            [2, 3, 5, 6, 8, 16]

    def test_payload_bits(self):
        assert float(payload_bits(1000, 8)) == 1000 * 8 + 64

    def test_pinned_mu_only(self):
        """Regression: quantize(x, b, mu=...) with phi=None must fall back
        to the tensor max for the top of the grid."""
        x = _rand((128,), lo=0.5, hi=2.0)
        codes, scale, mu = quantize(x, 8, mu=0.0)
        assert float(mu) == 0.0
        xq = dequantize(codes, scale, mu)
        assert np.isclose(float(xq.max()), float(x.max()), atol=1e-5)
        assert float(jnp.max(jnp.abs(x - xq))) <= float(scale) / 2 + 1e-6

    def test_pinned_phi_only(self):
        x = _rand((128,), lo=-2.0, hi=-0.5)
        codes, scale, mu = quantize(x, 8, phi=0.0)
        xq = dequantize(codes, scale, mu)
        assert np.isclose(float(xq.min()), float(x.min()), atol=1e-5)

    def test_stacked_wire_bits_counts_real_metadata(self):
        from repro.core.quantizer import quantize_stacked, stacked_wire_bits
        w = _rand((2, 16, 8))
        q8 = quantize_stacked(w, 8)                   # per-channel default
        assert stacked_wire_bits(q8) == 2 * 16 * 8 * 8 + 32 * 2 * (2 * 8)
        q8t = quantize_stacked(w, 8, per_channel=False)
        assert stacked_wire_bits(q8t) == 2 * 16 * 8 * 8 + 32 * 2 * 2
        q4 = quantize_stacked(w, 4)                   # packed: half codes
        assert stacked_wire_bits(q4) == 2 * 16 * 4 * 8 + 32 * 2 * (2 * 8)


class TestNoiseLaw:
    """Paper Eq. 18: ||sigma(b)||^2 = s * e^(-ln4 * b). The uniform
    quantizer's round-off energy must follow the 4^-b law and match the
    analytic scale s = n * range^2 / 12."""

    def test_exponent_matches_minus_ln4(self):
        x = _rand((4096,), seed=3)
        bits = np.arange(4, 10)
        energies = np.array([float(quant_noise_energy(x, int(b)))
                             for b in bits])
        slope = np.polyfit(bits, np.log(energies), 1)[0]
        assert abs(slope - (-LN4)) < 0.08 * LN4

    def test_analytic_scale_matches_measured(self):
        x = _rand((8192,), seed=7)
        s = float(analytic_noise_scale(x))
        for b in (6, 8):
            measured = float(quant_noise_energy(x, b))
            predicted = s * np.exp(-LN4 * b)
            assert 0.7 < measured / predicted < 1.4, (b, measured, predicted)


@settings(max_examples=25, deadline=None)
@given(bits=st.integers(2, 12), seed=st.integers(0, 10_000))
def test_property_noise_monotone_in_bits(bits, seed):
    """More bits never increases quantization noise (the monotonicity the
    solver's ceil-rounding relies on)."""
    x = _rand((512,), seed=seed)
    e1 = float(quant_noise_energy(x, bits))
    e2 = float(quant_noise_energy(x, bits + 1))
    assert e2 <= e1 + 1e-6


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), lo=st.floats(-10, 0), width=st.floats(0.1, 20))
def test_property_quantize_respects_range(seed, lo, width):
    x = _rand((256,), seed=seed, lo=lo, hi=lo + width)
    codes, scale, mu = quantize(x, 8)
    xq = dequantize(codes, scale, mu)
    assert float(xq.min()) >= lo - float(scale)
    assert float(xq.max()) <= lo + width + float(scale)
