"""Quantization-noise and accuracy-degradation model (paper Eq. 18–22,
following Zhou et al. AAAI'18 [33]).

Quantities per layer l of the model segment:

  s_l    — noise-energy scale at the OUTPUT (logits) caused by quantizing
           layer l: ``||sigma_l(b)||^2 = s_l * e^(-ln4 b)``. Calibrated by
           quantizing layer l at a probe bit-width b0 and measuring the
           output perturbation: s_l = E0 * 4^b0 (the exponential law is
           exact for uniform round-off noise; the linear propagation to the
           output preserves it in expectation).
  sigma* — adversarial noise: the minimal L2 perturbation of the final
           activation (logits) that flips the prediction. For an argmax
           classifier this has the closed form  (z_top1 - z_top2)/sqrt(2).
  rho_l  — robustness of layer l (Eq. 22): mean quantization noise energy
           over the calibration set / mean adversarial noise energy.
  psi_l  — accuracy-degradation measure (Eq. 20–21): ||sigma_l||^2 / rho_l,
           additive across layers.
  Delta(a) — constraint budget for accuracy degradation target a,
           calibrated by injecting output noise at increasing psi and
           measuring the empirical accuracy drop (Alg. 1 step 8).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantizer import fake_quant

PROBE_BITS = 8
# decades the Delta sweep may add above its base grid (a vocabulary of up
# to ~10^7 logits)
MAX_EXTRA_DECADES = 8
LN4 = float(np.log(4.0))


@dataclasses.dataclass
class LayerNoiseProfile:
    """Calibrated noise statistics for one partitionable layer."""
    s_w: float          # weight-quantization output-noise scale
    s_x: float          # activation-quantization output-noise scale
    rho: float          # robustness (Eq. 22)


@dataclasses.dataclass
class NoiseCalibration:
    layers: Sequence[LayerNoiseProfile]
    adv_noise_mean: float           # mean ||sigma*||^2 over the calib set
    delta_table: dict               # accuracy target a -> Delta budget

    def delta_for(self, a: float) -> float:
        """Largest tabulated budget whose degradation <= a (Alg. 2 step 1)."""
        keys = sorted(self.delta_table)
        best = self.delta_table[keys[0]]
        for k in keys:
            if k <= a:
                best = self.delta_table[k]
        return best


def adversarial_noise_energy(logits) -> jnp.ndarray:
    """||sigma*||^2 per example: minimal L2 logit perturbation flipping
    argmax = margin/sqrt(2), energy = margin^2/2."""
    top2 = jax.lax.top_k(logits, 2)[0]
    margin = top2[..., 0] - top2[..., 1]
    return jnp.square(margin) / 2.0


def output_noise_energy(apply_fn: Callable, params_clean, params_noisy, x):
    """||f(x; W') - f(x; W)||^2 summed over the batch."""
    clean = apply_fn(params_clean, x)
    noisy = apply_fn(params_noisy, x)
    d = (noisy - clean).astype(jnp.float32)
    return jnp.sum(jnp.square(d))


def calibrate_layer(apply_fn, params, x, layer_idx: int,
                    set_layer_weights, get_layer_weights,
                    activations, probe_bits: int = PROBE_BITS):
    """Measure (s_w, s_x) for one layer.

    ``set_layer_weights(params, idx, w)`` / ``get_layer_weights`` adapt the
    concrete parameter pytree; ``activations[idx]`` is the layer's input
    batch (for the activation-noise probe).
    """
    w = get_layer_weights(params, layer_idx)
    wq = jax.tree.map(lambda t: fake_quant(t, probe_bits), w)
    noisy = set_layer_weights(params, layer_idx, wq)
    e_w = output_noise_energy(apply_fn, params, noisy, x)
    s_w = float(e_w) * 4.0 ** probe_bits

    # activation probe: quantize the layer input, measure output deviation
    act = activations[layer_idx]
    act_q = fake_quant(act, probe_bits)

    def from_layer(a):
        return apply_fn(params, a, start=layer_idx)

    d = (from_layer(act_q) - from_layer(act)).astype(jnp.float32)
    e_x = float(jnp.sum(jnp.square(d)))
    s_x = e_x * 4.0 ** probe_bits
    return s_w, s_x


def backend_layer_energies(backend, x, probe_bits: int = PROBE_BITS):
    """Reference SCALAR probe loop for Alg. 1 steps 7–9 over a serving
    ``ModelBackend`` (duck-typed: only the protocol's forward family is
    touched). Per layer l: quantize the layer's weights / input
    activation at ``probe_bits`` and measure the squared logit
    perturbation — 1 full + 2 suffix forwards per layer, L times.

    This is the ground truth the backends' vectorized
    ``calibrate_probes`` overrides (one chunked ``lax.map`` over a
    "which layer is quantized" index, a single compiled program) are
    regression-locked against — tests and ``benchmarks/
    calibration_bench.py`` both compare against it.

    Returns (e_w (L,), e_x (L,), clean logits (B, C))."""
    acts, logits = backend.layer_activations(x)
    L = backend.num_layers
    e_w = np.zeros(L)
    e_x = np.zeros(L)
    for l in range(L):
        noisy = backend.with_layer_quantized(l, probe_bits)
        d_w = (backend.forward(x, params=noisy) - logits).astype(jnp.float32)
        e_w[l] = float(jnp.sum(jnp.square(d_w)))
        aq = fake_quant(acts[l], probe_bits)
        d = backend.forward_from_layer(aq, l) \
            - backend.forward_from_layer(acts[l], l)
        e_x[l] = float(jnp.sum(jnp.square(d.astype(jnp.float32))))
    return e_w, e_x, logits


def accuracy(apply_fn, params, x, y) -> float:
    logits = apply_fn(params, x)
    return float(jnp.mean(jnp.argmax(logits, -1) == y))


def calibrate_delta(apply_fn, params, x, y, rhos, targets,
                    key=None, trials: int = 3):
    """Map accuracy-degradation targets -> psi budgets Delta (Alg.1 step 8).

    Injects Gaussian noise of increasing energy on the logits, converts each
    energy to the psi it represents, and records the largest psi whose
    measured degradation stays within each target.
    """
    key = key if key is not None else jax.random.key(0)
    base = accuracy(apply_fn, params, x, y)
    logits = apply_fn(params, x)
    mean_rho = float(np.mean(rhos)) if len(rhos) else 1.0

    def degradation(i, psi):
        # psi = ||sigma||^2 / rho -> per-example output-noise energy
        energy = psi * mean_rho
        accs = []
        for t in range(trials):
            k = jax.random.fold_in(key, i * trials + t)
            g = jax.random.normal(k, logits.shape)
            g = g / jnp.maximum(
                jnp.linalg.norm(g, axis=-1, keepdims=True), 1e-12)
            noisy = logits + g * jnp.sqrt(energy)
            accs.append(float(jnp.mean(jnp.argmax(noisy, -1) == y)))
        return base - float(np.mean(accs))

    # Adaptive grid: degradation switches on when the per-example noise
    # energy approaches the adversarial energy, i.e. psi* ~ adv_mean / rho
    # (by Eq. 20–22). Sweep four decades below to one above.
    adv_mean = float(jnp.mean(adversarial_noise_energy(logits)))
    psi_star = max(adv_mean / max(mean_rho, 1e-30), 1e-12)
    psis = list(psi_star * np.logspace(-4, 1, 60))
    degr = [degradation(i, psi) for i, psi in enumerate(psis)]
    # Isotropic noise spreads over every class: with an LM's vocabulary
    # of V logits only ~1/V of its energy lies along the margin, so the
    # argmax flips far above psi*. Extend the sweep a decade at a time
    # until the largest target is exceeded, or the table would cap every
    # target at the grid's end.
    for _ in range(MAX_EXTRA_DECADES):
        if max(degr) > max(targets):
            break
        for psi in psis[-1] * np.logspace(1 / 12, 1, 12):
            degr.append(degradation(len(psis), psi))
            psis.append(psi)
    psis = np.asarray(psis)
    # enforce monotonicity (measurement noise) then invert
    degr = np.maximum.accumulate(np.asarray(degr))
    table = {}
    for a in targets:
        ok = psis[degr <= a + 1e-9]
        table[a] = float(ok[-1]) if len(ok) else float(psis[0])
    return table, base
