"""``ClassifierBackend`` — the paper's own MLP/CNN evaluation models
behind the ``ModelBackend`` protocol.

This is the code that used to be inlined across ``qpart_server.py`` and
``baselines.py`` (both reaching into ``repro.models.classifier``'s
private ``_apply_layer``/``_ensure_batched``); it now lives here once.

The forward family runs through the shared ``ModelBackend.jitted``
compile cache: ``forward``/``layer_activations`` compile once per input
shape, ``forward_from_layer`` and the device-segment prefix once per
(start/p, input shape) — classifier layer stacks are heterogeneous
(dense/conv), so the resume point stays a static trace parameter, but
L is small (4–6) and the caches make every path compile-once across
requests. ``calibrate_probes`` emits all L Alg. 1 noise energies from a
single compiled program (a ``lax.map`` over the "which layer is
quantized" index, selecting pre-quantized vs clean leaves per layer with
a scalar ``jnp.where``), regression-locked against the scalar loop in
``core.noise.backend_layer_energies``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.classifier import ClassifierConfig, DenseSpec
from repro.core import noise as noise_lib
from repro.core.cost_model import LayerSpec, classifier_layer_specs
from repro.core.partition import DeviceSegment, split_classifier
from repro.core.quantizer import fake_quant
from repro.models.classifier import (apply_layer, classifier_forward,
                                     ensure_batched, forward_from_layer,
                                     layer_activations)
from repro.serving.backends.base import ModelBackend


@dataclasses.dataclass
class ClassifierBackend(ModelBackend):
    """cfg: ClassifierConfig; params: list of per-layer {"w", "b"} dicts
    (``repro.models.classifier.init_classifier``)."""
    cfg: ClassifierConfig
    params: list

    @property
    def num_layers(self) -> int:
        return self.cfg.num_layers

    def layer_specs(self, batch: int = 1,
                    seq_len: Optional[int] = None) -> List[LayerSpec]:
        return self.refine_specs(classifier_layer_specs(self.cfg,
                                                        batch=batch),
                                 batch=batch)

    def input_elements(self) -> float:
        return float(np.prod(self.cfg.input_shape))

    # -- forward family (jitted, shape-keyed) ---------------------------
    def forward(self, x, params=None):
        fn = self.jitted(
            "forward", lambda: lambda p, a: classifier_forward(p, self.cfg, a))
        return fn(self.params if params is None else params, x)

    def forward_from_layer(self, a, start: int, params=None):
        fn = self.jitted(
            ("from_layer", start),
            lambda: lambda p, h: forward_from_layer(p, self.cfg, h, start))
        return fn(self.params if params is None else params, a)

    def layer_activations(self, x, params=None):
        fn = self.jitted(
            "acts", lambda: lambda p, a: layer_activations(p, self.cfg, a))
        return fn(self.params if params is None else params, x)

    def with_layer_quantized(self, layer: int, bits: int):
        noisy = list(self.params)
        noisy[layer] = {k: fake_quant(v, bits)
                        for k, v in self.params[layer].items()}
        return noisy

    # -- vectorized Alg. 1 probes ---------------------------------------
    def calibrate_probes(self, x, probe_bits: int = noise_lib.PROBE_BITS):
        """All L per-layer noise energies from ONE compiled program.

        Classifier activations have per-layer shapes, so instead of
        resuming from stacked activations (the transformer's trick) the
        e_x probe re-runs the forward with ``fake_quant`` injected at
        the entry of the selected layer; the clean side uses the SAME
        masked program with the no-layer sentinel l = -1, so both sides
        of the subtraction share one op sequence."""
        cfg, L = self.cfg, self.cfg.num_layers

        def probe_all(params, xx):
            h0 = ensure_batched(xx, cfg)
            if isinstance(cfg.layers[0], DenseSpec):
                h0 = h0.reshape(h0.shape[0], -1)
            qparams = [jax.tree.map(lambda t: fake_quant(t, probe_bits), p)
                       for p in params]
            logits = classifier_forward(params, cfg, xx)

            def act_quant_logits(l):
                h = h0
                for i, (spec, p) in enumerate(zip(cfg.layers, params)):
                    h = jnp.where(i == l, fake_quant(h, probe_bits), h)
                    h = apply_layer(spec, p, h, last=i == L - 1)
                return h

            clean = act_quant_logits(jnp.int32(-1))

            def probe(l):
                params_l = [jax.tree.map(
                    lambda c, q, i=i: jnp.where(i == l, q, c),
                    params[i], qparams[i]) for i in range(L)]
                d_w = classifier_forward(params_l, cfg, xx) - logits
                e_w = jnp.sum(jnp.square(d_w.astype(jnp.float32)))
                d_x = act_quant_logits(l) - clean
                e_x = jnp.sum(jnp.square(d_x.astype(jnp.float32)))
                return e_w, e_x

            e_w, e_x = jax.lax.map(probe, jnp.arange(L))
            return e_w, e_x, logits

        fn = self.jitted(("probe_all", probe_bits), lambda: probe_all)
        e_w, e_x, logits = fn(self.params, x)
        return np.asarray(e_w, np.float64), np.asarray(e_x, np.float64), \
            logits

    # -- device-segment execution ---------------------------------------
    def run_prefix(self, x, p: int, params=None):
        """Activation leaving layer p when layers 1..p run with ``params``
        (default: the backend's own; a device segment's quantized list or
        a baseline's pruned list both index the same way)."""
        def make():
            def f(prm, a):
                h = ensure_batched(a, self.cfg)
                if isinstance(self.cfg.layers[0], DenseSpec):
                    h = h.reshape(h.shape[0], -1)
                for l in range(p):
                    h = apply_layer(self.cfg.layers[l], prm[l], h,
                                    last=l == self.cfg.num_layers - 1)
                return h
            return f

        fn = self.jitted(("prefix", p), make)
        return fn(self.params if params is None else params, x)

    def split(self, plan) -> DeviceSegment:
        seg, _server = split_classifier(self.params, plan, self.layer_specs(),
                                        self.counters)
        return seg

    def run_device_segment(self, seg: DeviceSegment, plan, x):
        h = self.run_prefix(x, plan.p, params=seg.params)
        return fake_quant(h, int(seg.bits_x))
