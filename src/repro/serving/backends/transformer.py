"""``TransformerBackend`` — decoder LMs behind the ``ModelBackend``
protocol, so a transformer goes through the SAME calibrate →
``build_store`` → serve pipeline as the paper's classifiers.

Mapping onto the protocol:

  * partitionable layers = the decoder blocks (the embedding table always
    stays on-device — it starts the computation — and is not shipped, so
    it carries no payload term; ``transformer_layer_specs``'s embed row is
    dropped).
  * "logits" = next-token logits at the LAST sequence position, shape
    (B, V): the calibration's adversarial-margin and accuracy math
    (``core.noise``) applies unchanged, with y = the next token.
  * the whole forward family — ``forward``, ``forward_from_layer`` at
    EVERY resume point, ``layer_activations`` and the quantized
    ``run_device_segment`` — runs on ``transformer.segment_forward``'s
    masked ``lax.scan`` with DYNAMIC ``(start, stop)`` operands: one XLA
    compilation per input shape, not one per split point (DESIGN.md §7).
    The pre-PR-3 design kept a ``_jits`` dict with one jitted unrolled
    block loop per start — O(L) compilations of O(L) traced blocks.
  * ``calibrate_probes`` (Alg. 1 steps 7–9) emits all L per-layer noise
    energies from a single compiled program: a chunked ``lax.map`` over
    the "which layer is quantized" index, selecting the perturbed layer
    by masked ``jnp.where`` on the stacked period axis. Regression-locked
    against the scalar loop in ``core.noise.backend_layer_energies``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import noise as noise_lib
from repro.core.cost_model import (LayerSpec, kv_bytes_row as _kv_row,
                                   transformer_layer_specs)
from repro.core.partition import DeviceSegment, split_blocks
from repro.core.quantizer import fake_quant
from repro.kernels import ref
from repro.models import transformer as T
from repro.serving.backends.base import ModelBackend
from repro.serving.decode.cache import paged_kv_ctx

PROBE_CHUNK = 4      # layers probed per lax.map step (memory/parallelism)
_STACKED_CACHE_SLOTS = 4     # stacked quantized trees kept per backend


@dataclasses.dataclass
class TransformerBackend(ModelBackend):
    """cfg: ModelConfig; params: ``transformer.init_params`` tree.
    ``seq_len`` is the reference sequence length requests are planned at
    (inputs are token batches of shape (B, seq_len)); ``mode`` follows
    ``transformer_layer_specs`` ("prefill" | "decode")."""
    cfg: ModelConfig
    params: dict
    seq_len: int
    mode: str = "prefill"
    # context length decode streams are planned against (the KV cache is
    # allocated at this length). None = the backend is not planned for
    # decode and no cache-feasibility term is priced in — the prefill-
    # only pricing stays bit-identical.
    decode_max_len: Optional[int] = None
    # KV page size in ring slots (serving.decode.cache). None = legacy
    # worst-case reservation: every stream is priced at decode_max_len
    # context. Set -> admission prices streams at their page-rounded
    # ACTUAL context (prompt + max_new_tokens), admitting streams the
    # worst-case bound wrongly rejects.
    kv_page_tokens: Optional[int] = None

    supports_decode = True

    @property
    def num_layers(self) -> int:
        return self.cfg.num_layers

    def layer_specs(self, batch: int = 1,
                    seq_len: Optional[int] = None) -> List[LayerSpec]:
        specs = transformer_layer_specs(
            self.cfg, seq_len or self.seq_len, batch=batch,
            mode=self.mode)[1:]                      # drop the embed row
        return self.refine_specs(specs, batch=batch)

    def decode_layer_specs(self, batch: int = 1,
                           context_len: Optional[int] = None) -> List[LayerSpec]:
        """ONE decode step's per-layer terms at a ``context_len`` (default
        ``decode_max_len`` or ``seq_len``) context. HLO overrides
        (``set_layer_cost_overrides``) are measured on the PREFILL
        program, so they are deliberately NOT applied here."""
        ctx = context_len or self.decode_max_len or self.seq_len
        return transformer_layer_specs(self.cfg, ctx, batch=batch,
                                       mode="decode")[1:]

    def kv_bytes_row(self, batch: int = 1, tokens: Optional[int] = None):
        """Cumulative device-KV bytes by cut point for ONE decode stream.
        Default: the dense worst case (``decode_max_len`` ring slots per
        attention layer). With ``kv_page_tokens`` set and the stream's
        actual ``tokens`` (prompt + new tokens) given, the stream is
        priced at its page-rounded context instead — strictly <= the
        worst case, so the admission mask can only widen."""
        if self.decode_max_len is None:
            return None
        if tokens is None or self.kv_page_tokens is None:
            ctx = self.decode_max_len
        else:
            ctx = paged_kv_ctx(int(tokens), self.kv_page_tokens,
                               self.decode_max_len)
        cache = self.__dict__.setdefault("_kv_row_cache", {})
        key = (batch, ctx)
        row = cache.get(key)
        if row is None:
            row = cache[key] = _kv_row(
                self.decode_layer_specs(batch, context_len=ctx))
        return row

    def input_elements(self) -> float:
        return float(self.seq_len)                   # token ids per example

    # -- compile-once forward family ------------------------------------
    # Four programs total (ModelBackend.jitted: shape-keyed, trace-
    # counted), each taking the segment bounds as DYNAMIC operands:
    #   tokens_logits  (params, tokens, start, stop) -> (B, V)
    #   h_logits       (params, h,      start, stop) -> (B, V)
    #   acts           (params, tokens)              -> ((L,B,S,D), (B,V))
    #   cut            (params, tokens, stop)        -> (B, S, D)
    # Calibration probes re-enter them with perturbed params of the SAME
    # pytree structure, so the compile count stays O(1) in depth.
    def _tokens_logits(self):
        def f(params, tokens, start, stop):
            h = T.embed_tokens(params, self.cfg, tokens)
            return T.segment_logits(params, self.cfg, h, start, stop)
        return self.jitted("tokens_logits", lambda: f)

    def _h_logits(self):
        def f(params, h, start, stop):
            return T.segment_logits(params, self.cfg, h, start, stop)
        return self.jitted("h_logits", lambda: f)

    def _acts(self):
        def f(params, tokens):
            h = T.embed_tokens(params, self.cfg, tokens)
            h, acts = T.segment_forward(params, self.cfg, h, 0,
                                        self.num_layers, collect=True)
            return acts, T.unembed(params, self.cfg, h)[:, -1, :]
        return self.jitted("acts", lambda: f)

    def _cut(self):
        def f(params, tokens, stop):
            h = T.embed_tokens(params, self.cfg, tokens)
            return T.segment_forward(params, self.cfg, h, 0, stop)
        return self.jitted("cut", lambda: f)

    # -- compile-once decode programs (DESIGN.md §11) --------------------
    # Three more shape-keyed programs serve EVERY cut point of the
    # prefill→decode pipeline — (start, stop, pos) are dynamic operands
    # and the cache tree is an OPERAND (its max_len/dtype shape-key the
    # jit), so the device segment [0, p), the server tail [p, L) and
    # the monolithic [0, L) all reuse one compilation per shape:
    #   embed        (params, tokens)                        -> (B, S, D)
    #   prefill_seg  (params, h, cache0, start, stop)        -> (h, caches)
    #   decode_seg   (params, x, caches, pos, start, stop)   -> (x, caches)
    # Unembedding reuses ``h_logits`` with an EMPTY segment (start ==
    # stop == L): pure final-norm + head, no extra program.
    def _embed_prog(self):
        def f(params, tokens):
            return T.embed_tokens(params, self.cfg, tokens)
        return self.jitted("embed", lambda: f)

    def _prefill_seg(self):
        def f(params, h, cache0, start, stop):
            return T.segment_prefill(params, self.cfg, h, cache0, start,
                                     stop)
        return self.jitted("prefill_seg", lambda: f)

    def _decode_seg(self):
        def f(params, x, caches, pos, start, stop):
            return T.segment_decode_step(params, self.cfg, x, caches, pos,
                                         start, stop)
        return self.jitted("decode_seg", lambda: f)

    # -- chunked-prefill / speculative-verify programs (DESIGN.md §14) --
    # Two more shape-keyed programs with a DYNAMIC position offset, so
    # every chunk of every prompt — and every k-token verify batch —
    # reuses one compilation per (batch, s) shape:
    #   extend_seg  (params, h, caches, pos0, start, stop) -> (h, caches)
    #       chunked prefill: monolithic-prefill formula over the ring
    #   verify_seg  (params, h, caches, pos0, start, stop)
    #                                               -> (logits, caches)
    #       speculative verify: a lax.scan of the EXACT per-token decode
    #       step + unembed — one round trip, bitwise s sequential steps
    def _extend_seg(self):
        def f(params, h, caches, pos0, start, stop):
            return T.segment_extend(params, self.cfg, h, caches, pos0,
                                    start, stop)
        return self.jitted("extend_seg", lambda: f)

    def _verify_seg(self):
        def f(params, h, caches, pos0, start, stop):
            return T.segment_verify(params, self.cfg, h, caches, pos0,
                                    start, stop)
        return self.jitted("verify_seg", lambda: f)

    def embed(self, tokens, params=None):
        return self._embed_prog()(
            self.params if params is None else params, tokens)

    def prefill_segment(self, h, cache0, start, stop, params=None):
        return self._prefill_seg()(
            self.params if params is None else params, h, cache0, start,
            stop)

    def decode_segment(self, x, caches, pos, start, stop, params=None):
        return self._decode_seg()(
            self.params if params is None else params, x, caches, pos,
            start, stop)

    def extend_segment(self, h, caches, pos0, start, stop, params=None):
        """Chunked-prefill extend: blocks ``[start, stop)`` over the
        ``h`` rows entering at position ``pos0``, bitwise the monolithic
        ``segment_prefill`` formula (``T.segment_extend``)."""
        return self._extend_seg()(
            self.params if params is None else params, h, caches, pos0,
            start, stop)

    def verify_segment(self, h, caches, pos0, start, stop, params=None):
        """Speculative verify: the ``s`` drafted rows of ``h`` through
        blocks ``[start, stop)`` + per-row unembed in ONE program ->
        ``(logits (B, S, V), caches)`` — bitwise ``s`` sequential
        ``decode_segment`` + ``hidden_logits`` calls."""
        return self._verify_seg()(
            self.params if params is None else params, h, caches, pos0,
            start, stop)

    def hidden_logits(self, h, params=None):
        """Unembed hidden state ``h`` (B, S, D) -> (B, V) at the last
        position (empty segment of the shared ``h_logits`` program)."""
        return self._h_logits()(
            self.params if params is None else params, h,
            self.num_layers, self.num_layers)

    def forward(self, x, params=None):
        return self._tokens_logits()(
            self.params if params is None else params, x, 0, self.num_layers)

    def forward_from_layer(self, a, start: int, params=None):
        return self._h_logits()(
            self.params if params is None else params, a, start,
            self.num_layers)

    def layer_activations(self, x, params=None):
        acts, logits = self._acts()(
            self.params if params is None else params, x)
        return list(acts), logits

    def with_layer_quantized(self, layer: int, bits: int):
        plen = T.period_len(self.cfg)
        per, pos = divmod(layer, plen)
        blocks = list(self.params["blocks"])
        blocks[pos] = jax.tree.map(
            lambda t: t.at[per].set(fake_quant(t[per], bits)), blocks[pos])
        return {**self.params, "blocks": blocks}

    # -- vectorized Alg. 1 probes ---------------------------------------
    def calibrate_probes(self, x, probe_bits: int = noise_lib.PROBE_BITS,
                         chunk: int = PROBE_CHUNK):
        """All L per-layer noise energies from ONE compiled program.

        The probed model for layer l is selected functionally: every
        block's weights are pre-quantized per period slice (the same
        per-slice ``fake_quant`` as ``with_layer_quantized``) and the
        body of a chunked ``lax.map`` over l picks quantized vs clean
        leaves with a ``jnp.where`` mask on the stacked period axis — no
        per-layer params tree is ever rebuilt on the host. e_x probes
        resume from the stacked activations through the same masked
        segment forward ``forward_from_layer`` runs on."""
        L, plen = self.num_layers, T.period_len(self.cfg)
        nper = T.num_periods(self.cfg)
        cfg = self.cfg

        def probe_all(params, tokens):
            h0 = T.embed_tokens(params, cfg, tokens)
            h, acts = T.segment_forward(params, cfg, h0, 0, L, collect=True)
            logits = T.unembed(params, cfg, h)[:, -1, :]
            qblocks = [jax.tree.map(
                jax.vmap(lambda t: fake_quant(t, probe_bits)), bp)
                for bp in params["blocks"]]

            def probe(l):
                per = l // plen
                blocks_l = []
                for pos in range(plen):
                    sel = (jnp.arange(nper) == per) & (l % plen == pos)
                    blocks_l.append(jax.tree.map(
                        lambda c, q, sel=sel: jnp.where(
                            sel.reshape((nper,) + (1,) * (c.ndim - 1)),
                            q, c),
                        params["blocks"][pos], qblocks[pos]))
                params_l = {**params, "blocks": blocks_l}
                d_w = T.segment_logits(params_l, cfg, h0, 0, L) - logits
                e_w = jnp.sum(jnp.square(d_w.astype(jnp.float32)))
                a = acts[l]
                d_x = T.segment_logits(params, cfg, fake_quant(a, probe_bits),
                                       l, L) \
                    - T.segment_logits(params, cfg, a, l, L)
                e_x = jnp.sum(jnp.square(d_x.astype(jnp.float32)))
                return e_w, e_x

            e_w, e_x = jax.lax.map(probe, jnp.arange(L),
                                   batch_size=min(chunk, L))
            return e_w, e_x, logits

        fn = self.jitted(("probe_all", probe_bits, min(chunk, L)),
                         lambda: probe_all)
        e_w, e_x, logits = fn(self.params, x)
        return np.asarray(e_w, np.float64), np.asarray(e_x, np.float64), \
            logits

    # -- device-segment execution ---------------------------------------
    def _device_blocks(self, p: int):
        return [T.block_at(self.params, self.cfg, l)[0] for l in range(p)]

    def _stack_segment(self, seg_params: list):
        """Scatter the per-layer quantized trees back into the stacked
        period representation (full-precision beyond p — masked out by
        the segment forward's dynamic ``stop``), so the quantized device
        segment runs on the SAME compiled program as everything else."""
        plen = T.period_len(self.cfg)
        blocks = list(self.params["blocks"])
        for l, layer_tree in enumerate(seg_params):
            per, pos = divmod(l, plen)
            blocks[pos] = jax.tree.map(
                lambda full, q, per=per: full.at[per].set(q),
                blocks[pos], layer_tree)
        return {**self.params, "blocks": blocks}

    def split(self, plan) -> DeviceSegment:
        return split_blocks(self._device_blocks(plan.p), plan,
                            self.layer_specs())

    def stacked_for(self, seg: DeviceSegment, plan) -> dict:
        """The quantized segment scattered into a full stacked tree —
        built LAZILY on first execution (split alone — pricing, payload
        and memory queries — never pays for it) and cached per DEPLOYED
        plan on the backend, bounded: deployments sharing a plan (the
        common case — windows price onto few plans) share one copy, and
        N concurrent deployments never hold N model-size trees."""
        key = (plan.p, tuple(int(b) for b in np.asarray(seg.bits_w)),
               int(seg.bits_x))
        cache = self.__dict__.setdefault("_stacked_cache", {})
        if key not in cache:
            while len(cache) >= _STACKED_CACHE_SLOTS:
                cache.pop(next(iter(cache)))
            cache[key] = self._stack_segment(seg.params)
        return cache[key]

    def run_device_segment(self, seg: DeviceSegment, plan, x):
        h = self._cut()(self.stacked_for(seg, plan), x, plan.p)
        return fake_quant(h, int(seg.bits_x))

    # -- quantized-kernel device segment (PR 9) --------------------------
    def qstacked_for(self, seg: DeviceSegment, plan) -> dict:
        """``stacked_for``'s kernel twin: the routed projection/MLP
        weights (``transformer.KERNEL_ROUTED``) are carried as per-period
        quantized WIRE STRUCTS ({codes, scale, mu}) that ``models/``
        dispatch through the dequantize-fused qmatmul/qmatmul4 kernels,
        instead of pre-dequantized dense tensors. dequant(codes)
        reproduces ``split_blocks``' per-layer ``fake_quant`` exactly, so
        the numerics match the dense path up to matmul accumulation
        order. Struct trees key ONE extra jit program per decode entry
        point, but the pytree structure is CUT-INDEPENDENT (codes shapes
        depend only on the model and the packing layout), so the program
        count stays constant across cuts. Plans deploying > 8 bits fall
        back to ``stacked_for`` (the uint8 wire can't carry them)."""
        bits_w = [int(b) for b in np.asarray(seg.bits_w)]
        if any(b > 8 for b in bits_w):
            return self.stacked_for(seg, plan)
        key = (plan.p, tuple(bits_w), int(seg.bits_x))
        cache = self.__dict__.setdefault("_qstacked_cache", {})
        if key not in cache:
            while len(cache) >= _STACKED_CACHE_SLOTS:
                cache.pop(next(iter(cache)))
            cache[key] = self._build_qstacked(int(plan.p), bits_w)
        return cache[key]

    def _build_qstacked(self, p: int, bits_w: list) -> dict:
        """Build the struct tree: for each period position, routed leaves
        become per-period-per-tensor quantized structs at the deployed
        per-layer bit-widths (filler bits for periods beyond the cut —
        masked out by the dynamic ``stop``, values never observed);
        everything else (norms, biases, MoE expert stacks, SSM weights)
        is fake-quantized densely on the ACTIVE periods, mirroring
        ``_stack_segment`` + ``split_blocks`` leaf-for-leaf."""
        plen, nper = T.period_len(self.cfg), T.num_periods(self.cfg)

        def build_pos(pos: int):
            active = np.array([per * plen + pos < p for per in range(nper)])
            abits = [bits_w[per * plen + pos]
                     for per in range(nper) if active[per]]
            pack = bool(abits) and max(abits) <= 4
            fill = 4 if pack else 8
            bits = np.array([bits_w[per * plen + pos] if active[per]
                             else fill for per in range(nper)], np.float64)
            levels = jnp.asarray(2.0 ** bits - 1.0, jnp.float32)
            amask = jnp.asarray(active)

            def meta(leaf):
                axes = tuple(range(1, leaf.ndim))
                shape = (nper,) + (1,) * (leaf.ndim - 1)
                mu = jnp.min(leaf, axis=axes, keepdims=True)
                phi = jnp.max(leaf, axis=axes, keepdims=True)
                lv = levels.reshape(shape)
                scale = jnp.maximum((phi - mu) / lv, 1e-12)
                codes = jnp.clip(jnp.round((leaf - mu) / scale), 0, lv)
                return codes, scale, mu, lv

            def struct(leaf):
                codes, scale, mu, _ = meta(leaf)
                out = {"scale": scale.astype(jnp.float32),
                       "mu": mu.astype(jnp.float32)}
                if pack and leaf.shape[1] % 2 == 0:
                    # the qmatmul4 layout: row halves of the first
                    # contraction axis share a byte
                    out["codes_packed"] = ref.pack_int4_ref(codes, axis=1)
                else:
                    out["codes"] = codes.astype(jnp.uint8)
                return out

            def dense_fq(leaf):
                codes, scale, mu, _ = meta(leaf)
                fq = (codes.astype(jnp.float32) * scale
                      + mu).astype(leaf.dtype)
                mask = amask.reshape((nper,) + (1,) * (leaf.ndim - 1))
                return jnp.where(mask, fq, leaf)

            routed = T.KERNEL_ROUTED

            def walk(node, parent=None):
                if isinstance(node, dict):
                    return {k: (struct(v)
                                if parent in routed and k in routed[parent]
                                and not isinstance(v, dict)
                                else walk(v, k))
                            for k, v in node.items()}
                return dense_fq(node)

            return walk(self.params["blocks"][pos])

        return {**self.params,
                "blocks": [build_pos(pos) for pos in range(plen)]}
