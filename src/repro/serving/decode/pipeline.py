"""``DecodeSession`` — streaming greedy decode over the compile-once
segment programs, partitioned at the plan's cut point.

Prefill: the device embeds the prompt and runs its quantized segment
``[0, p)``, populating its own cache (stored at the deployed bit-width's
dtype, ``cache.kv_cache_dtype``); the cut hidden state crosses the
channel quantized at ``bits_x``; the server tail ``[p, L)`` fills its
full-precision cache and emits the first token (TTFT). Decode: each
step embeds the previous token on the device, advances the device
cache, ships ONE token's quantized hidden state, advances the server
cache and samples greedily. ``p == 0`` (full offload) runs entirely
server-side — the sampled token never has to cross the radio. ``p ==
L`` still unembeds server-side (the head weights stay with the server,
matching ``execute_plan``'s partition semantics), but dispatches no
server segment and holds no server cache: ``[L, L)`` is empty, as the
device side's ``[0, 0)`` is at ``p == 0``.

Every session of every cut point reuses the SAME jitted programs
(``TransformerBackend`` decode family): ``(start, stop, pos)`` are
dynamic operands and the cache tree is an operand, so ``trace_count``
is constant across cuts at a fixed (batch, prompt, max_len, dtype)
shape. Stage boundaries are wall-clock fenced (``block_until_ready``)
— the timings feed ``CalibrationLedger.record_decode``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ATTN
from repro.core.quantizer import dequantize, quantize
from repro.models import transformer as T
from repro.models.moe import MOE_STATS
from repro.serving.decode.cache import (DEFAULT_PAGE_TOKENS, KVPagePool,
                                        PagedKVCache, kv_cache_dtype,
                                        segment_cache_bytes,
                                        segment_nonattn_cache_bytes,
                                        segment_page_pool)
from repro.serving.errors import ServingError
from repro.serving.tracing import span


@dataclasses.dataclass
class GenerationResult:
    """One streamed generation. ``tokens`` (B, new_tokens) greedy ids;
    stage seconds are wall-clock, aggregated over the whole stream.

    Per-round semantics: generation advances in server ROUNDS — the
    prefill round emits token 0, then each decode round emits one token
    (plain greedy) or 1..k+1 tokens (a speculative draft/verify round).
    ``per_token_s`` stays length-consistent at ``new_tokens - 1``
    regardless: a round that emitted ``m`` tokens contributes ``m``
    equal entries of ``round_seconds / m``, so summing any slice of it
    still measures wall-clock. ``rounds`` counts decode rounds (the
    prefill is not a round); with speculation on, ``rounds <
    new_tokens - 1`` is exactly the round-trip amortization."""
    tokens: np.ndarray
    ttft_s: float                 # prefill → first token
    t_device_s: float             # device-segment seconds (incl. prefill)
    t_server_s: float             # server-tail seconds (incl. prefill)
    t_total_s: float
    per_token_s: List[float]      # per-token seconds (len new_tokens-1)
    device_cache_bytes: int       # resident [0, p) cache footprint
    server_cache_bytes: int       # resident [p, L) cache footprint
    device_cache_dtype: str
    rounds: int = 0               # decode rounds after the prefill
    draft_tokens: int = 0         # configured draft length k (0 = off)
    drafts_proposed: int = 0
    drafts_accepted: int = 0
    prefill_chunks: int = 1       # 1 = monolithic prefill

    @property
    def new_tokens(self) -> int:
        return int(self.tokens.shape[1])

    @property
    def tokens_per_s(self) -> float:
        """0.0 for a degenerate zero-duration window (clock granularity
        can collapse a tiny stream's wall time to 0)."""
        return self.new_tokens / self.t_total_s if self.t_total_s > 0 \
            else 0.0

    @property
    def accept_rate(self) -> Optional[float]:
        """Measured draft acceptance (accepted / proposed); None when no
        drafts were proposed (plain greedy or zero decode rounds)."""
        if self.drafts_proposed <= 0:
            return None
        return self.drafts_accepted / self.drafts_proposed


class DecodeSession:
    """One partitioned prefill→decode stream for a deployed plan.

    ``backend`` must support decode (``TransformerBackend``); ``segment``
    reuses an already-materialized quantized device segment (pass
    ``Deployment``'s). Prompts are token ids (B, S) — greedy text decode
    only; frontend archs (audio/vision) prefill from embeds and are not
    routed through sessions."""

    def __init__(self, backend, plan, *, max_len: int,
                 segment=None, qkernels: Optional[bool] = None,
                 paged: bool = False,
                 page_tokens: int = DEFAULT_PAGE_TOKENS,
                 page_pool: Optional[KVPagePool] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 draft_tokens: int = 0):
        if not getattr(backend, "supports_decode", False):
            raise ServingError(
                f"{type(backend).__name__} has no autoregressive decode "
                "path — decode sessions need a transformer backend")
        self.backend = backend
        self.plan = plan
        self.max_len = int(max_len)
        cfg = backend.cfg
        self.cfg = cfg
        if cfg.mla is not None:
            for knob, on in (("paged KV", paged),
                             ("chunked prefill",
                              prefill_chunk_tokens is not None),
                             ("speculative decode", bool(draft_tokens))):
                if on:
                    raise ServingError(
                        f"{knob} is not supported for latent attention "
                        "(MLA): its cache holds one latent and one rope key "
                        "per token, which the paged pool, the chunk planner "
                        "and the verify path do not lay out")
        self.L = backend.num_layers
        self.p = int(plan.p)
        self.model_dtype = getattr(jnp, cfg.dtype)
        if qkernels is None:
            # default: quantized-kernel device weights only where the
            # compiled kernels actually run (TPU); the CPU default stays
            # the pre-kernel dense fake-quant path bit-for-bit.
            from repro.kernels import ops
            qkernels = ops.kernel_mode() == "kernel" and \
                hasattr(backend, "qstacked_for")
        self.qkernels = bool(qkernels)
        if self.p > 0:
            seg = segment if segment is not None else backend.split(plan)
            self.dev_params = (backend.qstacked_for(seg, plan)
                               if self.qkernels
                               else backend.stacked_for(seg, plan))
            self.bits_x = int(seg.bits_x)
            self.dev_dtype = kv_cache_dtype(self.bits_x, self.model_dtype)
        else:
            self.dev_params = None
            self.bits_x = 0
            self.dev_dtype = self.model_dtype
        self.dev_caches = None
        self.srv_caches = None
        # the server's next-token logits (B, V) from the latest prefill
        # or decode step — what the greedy token was sampled from
        self.logits = None
        # block-granular device-KV accounting (cache.PagedKVCache): the
        # jitted programs keep their dense cache operands; the paged
        # structure tracks the page-granular RESIDENT footprint and is
        # validated bit-for-bit against the dense ring.
        self.paged = bool(paged) and self.p > 0
        self.page_tokens = int(page_tokens)
        self.page_pool = page_pool
        self.paged_kv: Optional[PagedKVCache] = None
        # serving-shape knobs (DESIGN.md §14), both default-off so the
        # zero-knob session is bit-for-bit the plain pipeline. Both rely
        # on slot == position in the ring (no wraparound) and on the
        # K/V cache being position-addressable, so they are gated to
        # attention-only, full-context (no sliding window) stacks.
        self.draft_tokens = int(draft_tokens)
        if self.draft_tokens < 0:
            raise ServingError("draft_tokens must be >= 0")
        plen = T.period_len(cfg)
        # full-context attention stacks prefill through the cache-
        # mediated extend program (monolithic prefill == the one-chunk
        # admission), so the prefill attention reads K/V through the
        # same narrowed cache dtype every later decode step reads —
        # and chunked prefill is bitwise the monolithic one
        self._cache_extendable = (
            cfg.sliding_window is None
            and all(cfg.block_kind(i) == ATTN for i in range(plen)))
        self.prefill_chunk_tokens: Optional[int] = None
        if prefill_chunk_tokens is not None or self.draft_tokens:
            if any(cfg.block_kind(i) != ATTN for i in range(plen)):
                raise ServingError(
                    "chunked prefill / speculative decode need an "
                    "attention-only stack: SSM state is a running "
                    "reduction, not position-addressable")
            if cfg.sliding_window is not None:
                raise ServingError(
                    "chunked prefill / speculative decode need the full-"
                    "context ring (slot == position); sliding-window "
                    "wraparound would overwrite live context")
        if prefill_chunk_tokens is not None:
            c = int(prefill_chunk_tokens) or 2 * self.page_tokens
            if c < 2:
                raise ServingError(
                    "prefill_chunk_tokens must be >= 2 (a 1-row chunk's "
                    "matvec lowering breaks the bitwise prefill lock) or "
                    "0 for the default of 2 * page_tokens")
            if self.paged and c % self.page_tokens:
                raise ServingError(
                    f"prefill_chunk_tokens={c} must be page-aligned "
                    f"(kv page = {self.page_tokens} tokens)")
            self.prefill_chunk_tokens = c
        # the routed layer's counters of this stream, (L, len(MOE_STATS))
        # int32 on the device, carried through every segment call and
        # read once, at the stream's end (``_read_moe_stats``)
        self.moe_stats = (jnp.zeros((self.L, len(MOE_STATS)), jnp.int32)
                          if T.moe_stats_enabled(cfg) else None)
        self.pos = 0
        self.t_device_s = 0.0
        self.t_server_s = 0.0
        self.rounds = 0
        self.drafts_proposed = 0
        self.drafts_accepted = 0
        self.prefill_chunks = 1

    # -- pricing views ---------------------------------------------------
    def wire_bits_per_token(self, batch: int) -> float:
        """Uplink bits per decode step: the quantized cut hidden state
        plus the 32-bit sampled-token downlink; 0 for full offload (the
        stream never touches the radio after the prompt upload)."""
        if self.p == 0:
            return 0.0
        return float(self.bits_x * self.cfg.d_model * batch + 32 * batch)

    def wire_bits_per_round(self, batch: int,
                            k: Optional[int] = None) -> float:
        """Wire bits for ONE speculative round: the device ships k
        drafted ids (32-bit) + k+1 quantized cut hiddens uplink and
        receives up to k+1 verified ids downlink. Bytes stay ~linear in
        tokens — the win over k+1 plain steps is ROUND TRIPS: one
        channel latency is paid per round instead of per token, which
        is the term that bounds tokens/s on a slow channel."""
        if self.p == 0:
            return 0.0
        k = self.draft_tokens if k is None else int(k)
        hidden = self.bits_x * self.cfg.d_model * batch
        return float((k + 1) * hidden + 32 * k * batch
                     + 32 * (k + 1) * batch)

    def _quant_hop(self, h):
        """Quantize the cut hidden ``h`` (B, S, D) for the channel hop
        with one grid PER TOKEN POSITION (min/max over that position's
        (B, 1, D) slab) — the grid a decode step uses for its
        single-token slab. Per-position grids make the hop partition-
        invariant: a chunk's rows quantize exactly as the monolithic
        prefill's same rows (a whole-tensor grid would couple every row
        to the prompt's global range and break the bitwise chunked ==
        monolithic lock), and a (B, 1, D) call reduces to the plain
        per-tensor ``fake_quant`` bit for bit (min/max are order-exact),
        so decode steps are unchanged."""
        mu = jnp.min(h, axis=(0, 2), keepdims=True)
        phi = jnp.max(h, axis=(0, 2), keepdims=True)
        codes, scale, mu = quantize(h, self.bits_x, mu=mu, phi=phi)
        return dequantize(codes, scale, mu, h.dtype)

    def device_cache_bytes(self) -> int:
        if self.dev_caches is None or self.p == 0:
            return 0
        if self.paged_kv is not None:
            # pages actually held + the dense non-attention remainder
            return self.paged_kv.resident_bytes + \
                segment_nonattn_cache_bytes(self.cfg, self.dev_caches, 0,
                                            self.p)
        return segment_cache_bytes(self.cfg, self.dev_caches, 0, self.p)

    def sever(self) -> int:
        """End the stream: return every held KV page to the pool (no-op
        for dense sessions). Returns the page count released."""
        if self.paged_kv is None:
            return 0
        return self.paged_kv.free_all()

    def server_cache_bytes(self) -> int:
        if self.srv_caches is None:
            return 0
        return segment_cache_bytes(self.cfg, self.srv_caches, self.p,
                                   self.L)

    # -- pipeline stages -------------------------------------------------
    def _segment(self, call, h, caches, pos, start, stop, **kw):
        """A segment program call, carrying the routed layer's counters
        through it where the model has them. -> (h, caches)."""
        if self.moe_stats is None:
            return call(h, caches, pos, start, stop, **kw)
        h, caches, self.moe_stats = call(h, caches, pos, start, stop,
                                         stats=self.moe_stats, **kw)
        return h, caches

    def _skip_server(self, h):
        """The server segment [p, L) is empty at p == L: no program is
        dispatched and no server cache is held. Its masked scan would
        return ``h`` unchanged and add nothing to the routed layer's
        counters. Counts ``segment.skip``; -> ``h``."""
        self.backend.counters["segment.skip"] += 1
        return h

    def _read_moe_stats(self) -> None:
        """Add the stream's routed-layer counters to the backend's
        ``moe.<name>`` counters: one device read, at the stream's end,
        inside a ``qpart.moe`` span whose arguments carry the totals and,
        per layer, the pairs on held experts and the held experts that
        got tokens (``pairs_here_by_layer``, ``experts_active_by_layer``:
        space-separated, as the trace's metadata takes no comma) for the
        trace's readers."""
        if self.moe_stats is None:
            return
        with span("moe") as sp:
            st = np.asarray(self.moe_stats)
            tot = st.sum(0)
            for name, v in zip(MOE_STATS, tot):
                self.backend.counters["moe." + name] += int(v)
            sp.set_metadata(
                **{name: int(v) for name, v in zip(MOE_STATS, tot)},
                experts_active_by_layer=" ".join(
                    str(int(v)) for v in st[:, 2]),
                pairs_here_by_layer=" ".join(str(int(v)) for v in st[:, 1]))
        self.moe_stats = jnp.zeros_like(self.moe_stats)

    @staticmethod
    def chunk_bounds(s: int, c: int) -> List[tuple]:
        """Chunk boundaries [(lo, hi), ...] covering ``[0, s)`` in
        ``c``-token chunks, folding a remainder of 1 into the final
        chunk — a 1-row chunk's matvec lowering would break the bitwise
        chunked == monolithic prefill lock (``_attn_extend_with_cache``)."""
        bounds, lo = [], 0
        while lo < s:
            hi = min(lo + c, s)
            if s - hi == 1:
                hi = s
            bounds.append((lo, hi))
            lo = hi
        return bounds

    def prefill(self, prompt):
        """Run the partitioned prefill; returns the first greedy token
        (B,) and records stage seconds (TTFT = their sum). With
        ``prefill_chunk_tokens`` set the prompt is admitted chunk by
        chunk through ``extend_segment`` — same caches and first token
        bit-for-bit (lossless storage), but the compiled programs are
        shape-keyed on the CHUNK length, so a new prompt length no
        longer costs a fresh XLA trace and TTFT stops scaling with it."""
        prompt = jnp.asarray(prompt, jnp.int32)
        b, s = prompt.shape
        if s + 1 > self.max_len:
            raise ServingError(
                f"prompt ({s}) leaves no room in max_len={self.max_len}")
        if self.prefill_chunk_tokens is not None:
            return self._prefill_chunked(prompt, b, s,
                                         self.prefill_chunk_tokens)
        if self._cache_extendable:
            # monolithic prefill IS the one-chunk admission: routing it
            # through the same cache-mediated extend program means the
            # prefill attention reads K/V through the narrowed device
            # cache dtype — exactly what every decode step reads — and
            # a chunked prefill is bitwise this monolithic one (a
            # direct ``prefill_segment`` would attend on full-precision
            # K/V the cache then rounds, an answer no later step can
            # reproduce)
            return self._prefill_chunked(prompt, b, s, None)
        t0 = time.perf_counter()
        if self.p > 0:
            with span("device"):
                h0 = self.backend.embed(prompt, params=self.dev_params)
                cache0 = T.init_cache(self.cfg, b, self.max_len,
                                      self.dev_dtype)
                h_dev, self.dev_caches = self.backend.prefill_segment(
                    h0, cache0, 0, self.p, params=self.dev_params)
            with span("hop"):
                h_in = self._quant_hop(h_dev)
            with span("fence"):
                jax.block_until_ready(h_in)
            if self.paged:
                if self.page_pool is None:
                    self.page_pool = segment_page_pool(
                        self.cfg, 0, self.p, b, self.max_len,
                        self.dev_dtype, page_tokens=self.page_tokens)
                self.paged_kv = PagedKVCache(self.page_pool, self.cfg, 0,
                                             self.p, b, self.max_len)
                self.paged_kv.ingest_prefill(self.dev_caches, s)
        t1 = time.perf_counter()
        if self.p < self.L:
            with span("server"):
                if self.p == 0:
                    h_in = self.backend.embed(prompt)
                cache0 = T.init_cache(self.cfg, b, self.max_len,
                                      self.model_dtype)
                h_srv, self.srv_caches = self.backend.prefill_segment(
                    h_in, cache0, self.p, self.L)
        else:
            h_srv = self._skip_server(h_in)
        with span("unembed"):
            self.logits = self.backend.hidden_logits(h_srv[:, -1:, :])
            token = jnp.argmax(self.logits, -1).astype(jnp.int32)
        with span("sync"):
            jax.block_until_ready(token)
        t2 = time.perf_counter()
        self.t_device_s += t1 - t0
        self.t_server_s += t2 - t1
        self.pos = s
        return token

    def _prefill_chunked(self, prompt, b: int, s: int,
                         chunk_tokens: Optional[int]):
        """Chunk-granular prefill (``chunk_tokens=None`` = one chunk —
        the monolithic case): each chunk runs device extend → quantized
        hop → server extend, and (when paged) its pages are ingested as
        it lands — the paged footprint grows with the admitted prefix,
        not the final prompt."""
        bounds = [(0, s)] if chunk_tokens is None \
            else self.chunk_bounds(s, chunk_tokens)
        self.prefill_chunks = len(bounds)
        if self.p > 0:
            self.dev_caches = T.init_cache(self.cfg, b, self.max_len,
                                           self.dev_dtype)
            if self.paged:
                if self.page_pool is None:
                    self.page_pool = segment_page_pool(
                        self.cfg, 0, self.p, b, self.max_len,
                        self.dev_dtype, page_tokens=self.page_tokens)
                self.paged_kv = PagedKVCache(self.page_pool, self.cfg, 0,
                                             self.p, b, self.max_len)
        if self.p < self.L:
            self.srv_caches = T.init_cache(self.cfg, b, self.max_len,
                                           self.model_dtype)
        h_srv = None
        for lo, hi in bounds:
            chunk = prompt[:, lo:hi]
            pos0 = jnp.asarray(lo, jnp.int32)
            t0 = time.perf_counter()
            if self.p > 0:
                with span("device"):
                    h0 = self.backend.embed(chunk, params=self.dev_params)
                    h_dev, self.dev_caches = self._segment(
                        self.backend.extend_segment, h0, self.dev_caches,
                        pos0, 0, self.p, params=self.dev_params)
                with span("hop"):
                    h_in = self._quant_hop(h_dev)
                with span("fence"):
                    jax.block_until_ready(h_in)
                if self.paged_kv is not None:
                    self.paged_kv.ingest_range(self.dev_caches, lo, hi)
            t1 = time.perf_counter()
            if self.p < self.L:
                with span("server"):
                    if self.p == 0:
                        h_in = self.backend.embed(chunk)
                    h_srv, self.srv_caches = self._segment(
                        self.backend.extend_segment, h_in, self.srv_caches,
                        pos0, self.p, self.L)
                with span("fence"):
                    jax.block_until_ready(h_srv)
            else:
                h_srv = self._skip_server(h_in)
            t2 = time.perf_counter()
            self.t_device_s += t1 - t0
            self.t_server_s += t2 - t1
        t1 = time.perf_counter()
        with span("unembed"):
            self.logits = self.backend.hidden_logits(h_srv[:, -1:, :])
            token = jnp.argmax(self.logits, -1).astype(jnp.int32)
        with span("sync"):
            jax.block_until_ready(token)
        self.t_server_s += time.perf_counter() - t1
        self.pos = s
        return token

    def step(self, token):
        """One decode step feeding ``token`` (B,); returns the next
        greedy token (B,)."""
        if self.pos + 1 > self.max_len:
            raise ServingError(f"decode past max_len={self.max_len}")
        tok = jnp.asarray(token, jnp.int32).reshape(-1, 1)
        pos = jnp.asarray(self.pos, jnp.int32)
        t0 = time.perf_counter()
        if self.p > 0:
            with span("device"):
                x = self.backend.embed(tok, params=self.dev_params)
                x_dev, self.dev_caches = self._segment(
                    self.backend.decode_segment, x, self.dev_caches, pos, 0,
                    self.p, params=self.dev_params)
            with span("hop"):
                x_in = self._quant_hop(x_dev)
            with span("fence"):
                jax.block_until_ready(x_in)
            if self.paged_kv is not None:
                self.paged_kv.append_step(self.dev_caches, self.pos)
        t1 = time.perf_counter()
        if self.p < self.L:
            with span("server"):
                if self.p == 0:
                    x_in = self.backend.embed(tok)
                x_srv, self.srv_caches = self._segment(
                    self.backend.decode_segment, x_in, self.srv_caches, pos,
                    self.p, self.L)
        else:
            x_srv = self._skip_server(x_in)
        with span("unembed"):
            self.logits = self.backend.hidden_logits(x_srv)
            nxt = jnp.argmax(self.logits, -1).astype(jnp.int32)
        with span("sync"):
            jax.block_until_ready(nxt)
        t2 = time.perf_counter()
        self.t_device_s += t1 - t0
        self.t_server_s += t2 - t1
        self.pos += 1
        return nxt

    def _spec_round(self, token, k: int) -> List[np.ndarray]:
        """One speculative round: draft ``k`` tokens through the device
        segment + draft head, verify all of them in ONE server call,
        emit the longest matching greedy prefix + the server's next
        token (1..k+1 tokens) — bit-identical to plain greedy decode.

        Draft head: argmax over ``hidden_logits`` of the QUANTIZED cut
        hidden — the deployed segment at its planned bit-widths IS the
        draft model (at p == L it is the full model, so acceptance is
        exactly 1; at p == 0 it degenerates to an embedding-only guess).
        No cache rollback on rejection: every slot past the acceptance
        point is re-written by a later round before any query attends
        it (slot == position, writes precede reads), so stale draft K/V
        is unreachable by construction."""
        P = self.pos
        t0 = time.perf_counter()
        cur = jnp.asarray(token, jnp.int32).reshape(-1, 1)
        qs: List = []
        drafts: List = []
        for j in range(k + 1):
            pos = jnp.asarray(P + j, jnp.int32)
            if self.p > 0:
                x = self.backend.embed(cur, params=self.dev_params)
                x_dev, self.dev_caches = self.backend.decode_segment(
                    x, self.dev_caches, pos, 0, self.p,
                    params=self.dev_params)
                q = self._quant_hop(x_dev)
            else:
                q = self.backend.embed(cur)
            qs.append(q)
            if j < k:
                d = jnp.argmax(
                    self.backend.hidden_logits(q, params=self.dev_params),
                    -1).astype(jnp.int32)
                drafts.append(np.asarray(d))
                cur = d.reshape(-1, 1)
        hh = jnp.concatenate(qs, axis=1)           # (B, k+1, D)
        jax.block_until_ready(hh)
        if self.paged_kv is not None:
            for j in range(k + 1):
                self.paged_kv.append_step(self.dev_caches, P + j)
        t1 = time.perf_counter()
        if self.srv_caches is None:
            # p == L: the prefill held no server cache; verify's masked
            # scan over the empty [L, L) writes none, so a fresh one is
            # what the prefill would have left
            self.srv_caches = T.init_cache(self.cfg, hh.shape[0],
                                           self.max_len, self.model_dtype)
        logits, self.srv_caches = self.backend.verify_segment(
            hh, self.srv_caches, jnp.asarray(P, jnp.int32), self.p,
            self.L)
        g = np.asarray(jnp.argmax(logits, -1).astype(jnp.int32))
        t2 = time.perf_counter()
        # acceptance = longest prefix where every batch row's draft
        # matches the verified greedy token (min over rows keeps all
        # rows on their true greedy trajectory)
        d_np = np.stack(drafts, axis=1)            # (B, k)
        a = k
        for i in range(k):
            if not np.array_equal(d_np[:, i], g[:, i]):
                a = i
                break
        if self.p > 0:
            self.t_device_s += t1 - t0
        else:
            self.t_server_s += t1 - t0
        self.t_server_s += t2 - t1
        self.drafts_proposed += k
        self.drafts_accepted += a
        self.pos = P + a + 1
        return [g[:, i] for i in range(a + 1)]

    # -- drivers ----------------------------------------------------------
    def round_stream(self, prompt, max_new_tokens: int):
        """Generator of per-round token lists: the first yield is the
        prefill's ``[token0]``; each later yield is one decode round's
        emissions — ``[token]`` for plain greedy, 1..k+1 tokens for a
        speculative round. ``self.rounds`` counts the decode rounds."""
        with span("prefill", tokens=int(np.prod(np.shape(prompt))),
                  p=self.p):
            token = self.prefill(prompt)
            out = [np.asarray(token)]
        if max_new_tokens <= 1:
            self._read_moe_stats()
        yield out
        emitted = 1
        while emitted < max_new_tokens:
            remaining = max_new_tokens - emitted
            k = min(self.draft_tokens, remaining - 1,
                    self.max_len - 1 - self.pos)
            if k >= 1:
                out = self._spec_round(token, k)
                token = jnp.asarray(out[-1], jnp.int32)
            else:
                with span("step", pos=self.pos):
                    token = self.step(token)
                    out = [np.asarray(token)]
            self.rounds += 1
            emitted += len(out)
            if emitted >= max_new_tokens:
                self._read_moe_stats()
            yield out

    def stream(self, prompt, max_new_tokens: int):
        """Generator of (step_index, token (B,) np.ndarray) — token 0 is
        the prefill's (TTFT); the session's stage clocks accumulate as
        the consumer drains it. A speculative round's tokens are yielded
        individually (they become available together)."""
        i = 0
        for out in self.round_stream(prompt, max_new_tokens):
            for tok in out:
                yield i, tok
                i += 1

    def generate(self, prompt, max_new_tokens: int,
                 stream_cb=None) -> GenerationResult:
        if max_new_tokens < 1:
            raise ServingError("max_new_tokens must be >= 1")
        toks: List[np.ndarray] = []
        per_token: List[float] = []
        t_start = time.perf_counter()
        ttft = None
        last = t_start
        i = 0
        for out in self.round_stream(prompt, max_new_tokens):
            now = time.perf_counter()
            if ttft is None:
                ttft = now - t_start
            else:
                # spread the round's wall seconds over its emissions so
                # len(per_token_s) == new_tokens - 1 (docstring above)
                per_token.extend([(now - last) / len(out)] * len(out))
            last = now
            for tok in out:
                toks.append(tok)
                if stream_cb is not None:
                    stream_cb(i, tok)
                i += 1
        total = time.perf_counter() - t_start
        return GenerationResult(
            tokens=np.stack(toks, axis=1),
            ttft_s=float(ttft),
            t_device_s=self.t_device_s,
            t_server_s=self.t_server_s,
            t_total_s=total,
            per_token_s=per_token,
            device_cache_bytes=self.device_cache_bytes(),
            server_cache_bytes=self.server_cache_bytes(),
            device_cache_dtype=np.dtype(self.dev_dtype).name,
            rounds=self.rounds,
            draft_tokens=self.draft_tokens,
            drafts_proposed=self.drafts_proposed,
            drafts_accepted=self.drafts_accepted,
            prefill_chunks=self.prefill_chunks)
