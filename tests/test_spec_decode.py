"""Speculative decode at the cut point + chunked prefill (DESIGN.md
§14): the bitwise lock speculative output == plain greedy output across
cut points and draft lengths, chunked prefill rebuilding the monolithic
KV caches bit for bit at any chunk size, compile-once across prompt
lengths, the gating errors, acceptance-rate pricing plumbing
(``expected_tokens_per_round`` / ledger pooling / chunk pricing rows),
and the fleet engine's PREFILL_CHUNK lane + speculative rounds with
their replay and zero-knob bit-identity contracts."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_shim import given, settings, st
from repro.configs.base import get_config
from repro.core.cost_model import (CalibrationLedger, Channel,
                                   DeviceProfile, ObjectiveWeights,
                                   ServerProfile,
                                   expected_tokens_per_round)
from repro.core.solver import PartitionPlan
from repro.models import transformer as T
from repro.serving.backends import TransformerBackend
from repro.serving.decode import DecodeSession
from repro.serving.engine import FleetEngine
from repro.serving.engine.faults import (DISCONNECT, RECONNECT, FaultEvent)
from repro.serving.errors import ServingError
from repro.serving.pricing import candidate_rows_for, prefill_chunk_rows_for
from repro.serving.qpart_server import QPARTServer
from repro.serving.simulator import InferenceRequest
from repro.serving.testing import stub_transformer_calibration

pytestmark = pytest.mark.smoke

KEY = jax.random.key(0)
SEQ = 16
MAX_LEN = 48


def _f32(cfg):
    return dataclasses.replace(cfg, dtype="float32")


def _manual_plan(p: int, bits: float = 16.0) -> PartitionPlan:
    return PartitionPlan(p=p, bits_w=np.full(p, float(bits)),
                         bits_x=float(bits), objective=0.0, psi_total=0.0,
                         payload_bits=0.0, breakdown={})


@pytest.fixture(scope="module")
def lm():
    cfg = dataclasses.replace(
        get_config("smollm-135m").reduced(), name="smollm-spec",
        d_model=64, num_heads=2, num_kv_heads=1, head_dim=32, d_ff=128,
        vocab_size=32, tp_pad=1, dtype="float32")
    return cfg, T.init_params(KEY, cfg)


@pytest.fixture(scope="module")
def backend(lm):
    cfg, params = lm
    return TransformerBackend(cfg, params, seq_len=SEQ,
                              decode_max_len=MAX_LEN)


def _prompt(cfg, s=8, b=1, seed=0):
    return np.asarray(jax.random.randint(jax.random.key(seed), (b, s), 0,
                                         cfg.vocab_size))


def _cache_trees_equal(a, b) -> bool:
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    return ta == tb and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


class TestSpecBitIdentity:
    """The lock every test hangs off: speculative decode emits the
    EXACT plain-greedy token sequence — verify-by-scan makes the
    verified rows bit-identical to sequential decode steps, so the
    accepted prefix can never diverge from the greedy trajectory."""

    def _generate(self, backend, p, prompt, n, **kw):
        s = DecodeSession(backend, _manual_plan(p), max_len=MAX_LEN, **kw)
        return s, s.generate(prompt, n)

    def test_spec_equals_greedy_across_cuts_and_k(self, lm, backend):
        cfg, _ = lm
        L = cfg.num_layers
        prompt = _prompt(cfg)
        for p in sorted({0, 1, L // 2, L}):
            _, ref = self._generate(backend, p, prompt, 10)
            for k in (1, 2, 3):
                _, out = self._generate(backend, p, prompt, 10,
                                        draft_tokens=k)
                assert np.array_equal(out.tokens, ref.tokens), \
                    f"spec (p={p}, k={k}) diverged from greedy"
                assert out.draft_tokens == k
                assert out.drafts_proposed > 0

    def test_full_device_cut_accepts_everything(self, lm, backend):
        """At p == L the draft head IS the verify head (the full model
        runs on the device; the server only unembeds), so every draft
        is accepted and rounds shrink as k grows."""
        cfg, _ = lm
        prompt = _prompt(cfg)
        rounds = []
        for k in (1, 2, 3):
            _, out = self._generate(backend, cfg.num_layers, prompt, 10,
                                    draft_tokens=k)
            assert out.accept_rate == 1.0
            rounds.append(out.rounds)
        assert rounds[0] >= rounds[1] >= rounds[2]
        assert rounds[0] > rounds[2]
        assert rounds[2] < 10 - 1   # strictly fewer rounds than tokens

    def test_batched_prompts_stay_greedy(self, lm, backend):
        """Acceptance is the min over batch rows — every row stays on
        its own greedy trajectory even when rows diverge."""
        cfg, _ = lm
        prompt = _prompt(cfg, b=3, seed=5)
        _, ref = self._generate(backend, 1, prompt, 8)
        _, out = self._generate(backend, 1, prompt, 8, draft_tokens=2)
        assert np.array_equal(out.tokens, ref.tokens)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 999), st.integers(1, 4), st.integers(2, 12))
    def test_property_spec_equals_greedy(self, lm, backend, seed, k, n):
        """For ANY seeded prompt, draft length, and generation length,
        speculative output == plain greedy output (cut fixed at 1 — the
        cut sweep is the deterministic test above)."""
        cfg, _ = lm
        prompt = _prompt(cfg, seed=seed)
        _, ref = self._generate(backend, 1, prompt, n)
        _, out = self._generate(backend, 1, prompt, n, draft_tokens=k)
        assert np.array_equal(out.tokens, ref.tokens)


class TestChunkedPrefill:
    def _sessions(self, backend, p, chunk, bits=16.0, **kw):
        mono = DecodeSession(backend, _manual_plan(p, bits),
                             max_len=MAX_LEN, **kw)
        chnk = DecodeSession(backend, _manual_plan(p, bits),
                             max_len=MAX_LEN,
                             prefill_chunk_tokens=chunk, **kw)
        return mono, chnk

    def test_chunk_bounds_folds_remainder_of_one(self):
        assert DecodeSession.chunk_bounds(8, 4) == [(0, 4), (4, 8)]
        assert DecodeSession.chunk_bounds(9, 4) == [(0, 4), (4, 9)]
        assert DecodeSession.chunk_bounds(10, 4) == [(0, 4), (4, 8),
                                                     (8, 10)]
        assert DecodeSession.chunk_bounds(3, 4) == [(0, 3)]
        # no (lo, hi) with hi - lo == 1 for any (s, c >= 2)
        for s in range(2, 20):
            for c in range(2, 8):
                assert all(hi - lo >= 2
                           for lo, hi in DecodeSession.chunk_bounds(s, c))

    def test_chunked_equals_monolithic_tokens(self, lm, backend):
        cfg, _ = lm
        prompt = _prompt(cfg, s=11, seed=3)
        for chunk in (2, 4, 5):
            mono, chnk = self._sessions(backend, 1, chunk)
            ref = mono.generate(prompt, 8)
            out = chnk.generate(prompt, 8)
            assert np.array_equal(out.tokens, ref.tokens)
            assert out.prefill_chunks == len(
                DecodeSession.chunk_bounds(11, chunk))
            assert ref.prefill_chunks == 1

    def test_chunked_rebuilds_caches_bitwise(self, lm, backend):
        """At a lossless device bit-width (32) the chunked prefill must
        rebuild BOTH segment caches bit for bit — same floats, not just
        same argmax."""
        cfg, _ = lm
        prompt = _prompt(cfg, s=13, seed=7)
        for chunk in (2, 4, 6):
            mono, chnk = self._sessions(backend, 1, chunk, bits=32.0)
            t_ref = mono.prefill(prompt)
            t_out = chnk.prefill(prompt)
            assert np.array_equal(np.asarray(t_out), np.asarray(t_ref))
            assert _cache_trees_equal(chnk.dev_caches, mono.dev_caches)
            assert _cache_trees_equal(chnk.srv_caches, mono.srv_caches)

    @settings(max_examples=8, deadline=None)
    @given(st.integers(2, 9), st.integers(4, 14))
    def test_property_any_chunk_size_rebuilds_prefill(self, lm, backend,
                                                      chunk, s_len):
        cfg, _ = lm
        prompt = _prompt(cfg, s=s_len, seed=chunk * 31 + s_len)
        mono, chnk = self._sessions(backend, 1, chunk, bits=32.0)
        t_ref = mono.prefill(prompt)
        t_out = chnk.prefill(prompt)
        assert np.array_equal(np.asarray(t_out), np.asarray(t_ref))
        assert _cache_trees_equal(chnk.srv_caches, mono.srv_caches)


class TestCompileOnce:
    def test_chunked_prefill_is_prompt_length_blind(self, lm, backend):
        """The chunk programs are shape-keyed on the CHUNK length:
        after the first chunked generation, new PROMPT lengths cost
        zero fresh XLA traces — the mechanism that decouples TTFT from
        prompt length (a monolithic prefill re-traces per length)."""
        cfg, _ = lm
        s0 = DecodeSession(backend, _manual_plan(1), max_len=MAX_LEN,
                           prefill_chunk_tokens=4)
        s0.generate(_prompt(cfg, s=8), 3)
        traces = backend.trace_count
        for s_len in (10, 12, 14):
            s = DecodeSession(backend, _manual_plan(1), max_len=MAX_LEN,
                              prefill_chunk_tokens=4)
            s.generate(_prompt(cfg, s=s_len, seed=s_len), 3)
        assert backend.trace_count == traces, \
            "chunked prefill re-traced on a new prompt length"

    def test_spec_rounds_share_programs_across_cuts(self, lm, backend):
        """(start, stop, pos) are dynamic operands of the draft/verify
        programs too: a second speculative session at a DIFFERENT cut
        compiles nothing new."""
        cfg, _ = lm
        prompt = _prompt(cfg)
        s0 = DecodeSession(backend, _manual_plan(1), max_len=MAX_LEN,
                           draft_tokens=2)
        s0.generate(prompt, 6)
        traces = backend.trace_count
        s1 = DecodeSession(backend, _manual_plan(2), max_len=MAX_LEN,
                           draft_tokens=2)
        s1.generate(prompt, 6)
        assert backend.trace_count == traces, \
            "speculative round re-traced on a new cut"


class TestGatesAndGuards:
    def test_ssm_stack_rejects_spec_and_chunking(self):
        cfg = _f32(get_config("mamba2-1.3b").reduced())
        params = T.init_params(KEY, cfg)
        backend = TransformerBackend(cfg, params, seq_len=SEQ,
                                     decode_max_len=MAX_LEN)
        with pytest.raises(ServingError, match="attention-only"):
            DecodeSession(backend, _manual_plan(1), max_len=MAX_LEN,
                          draft_tokens=2)
        with pytest.raises(ServingError, match="attention-only"):
            DecodeSession(backend, _manual_plan(1), max_len=MAX_LEN,
                          prefill_chunk_tokens=4)

    def test_sliding_window_rejects_spec_and_chunking(self, lm):
        cfg, params = lm
        cfg = dataclasses.replace(cfg, sliding_window=8)
        backend = TransformerBackend(cfg, params, seq_len=SEQ,
                                     decode_max_len=MAX_LEN)
        with pytest.raises(ServingError, match="sliding-window"):
            DecodeSession(backend, _manual_plan(1), max_len=MAX_LEN,
                          draft_tokens=1)

    def test_bad_knob_values_reject(self, backend):
        with pytest.raises(ServingError, match="draft_tokens"):
            DecodeSession(backend, _manual_plan(1), max_len=MAX_LEN,
                          draft_tokens=-1)
        with pytest.raises(ServingError, match=">= 2"):
            DecodeSession(backend, _manual_plan(1), max_len=MAX_LEN,
                          prefill_chunk_tokens=1)

    def test_result_guards(self, lm, backend):
        """Degenerate-window guard + per-round accounting: tokens_per_s
        is 0.0 (not a ZeroDivisionError) on a zero-duration window;
        per_token_s stays length new_tokens - 1 when rounds emit >1
        token; accept_rate is None until a draft is proposed."""
        cfg, _ = lm
        s = DecodeSession(backend, _manual_plan(cfg.num_layers),
                          max_len=MAX_LEN, draft_tokens=3)
        out = s.generate(_prompt(cfg), 9)
        assert len(out.per_token_s) == out.new_tokens - 1
        assert out.rounds < out.new_tokens - 1   # amortization happened
        assert np.isclose(sum(out.per_token_s),
                          out.t_total_s - out.ttft_s, rtol=0.2) \
            or out.t_total_s < 1e-3
        zero = dataclasses.replace(out, t_total_s=0.0)
        assert zero.tokens_per_s == 0.0
        plain = DecodeSession(backend, _manual_plan(1), max_len=MAX_LEN)
        ref = plain.generate(_prompt(cfg), 3)
        assert ref.accept_rate is None and ref.rounds == 2


class TestPricingHooks:
    def test_expected_tokens_per_round(self):
        assert expected_tokens_per_round(0, 0.5) == 1.0
        assert expected_tokens_per_round(3, 0.0) == 1.0
        assert expected_tokens_per_round(3, 1.0) == 4.0
        assert expected_tokens_per_round(4, 0.5) == 3.0
        with pytest.raises(ValueError, match="draft_k"):
            expected_tokens_per_round(-1, 0.5)
        with pytest.raises(ValueError, match="accept_rate"):
            expected_tokens_per_round(2, 1.5)

    @pytest.fixture()
    def stub(self):
        cfg = _f32(get_config("smollm-135m").reduced())
        dev = DeviceProfile(memory_bytes=2e9)
        ch = Channel(capacity_bps=2e6)
        w = ObjectiveWeights()
        srv = QPARTServer()
        stub_transformer_calibration(srv, "lm", cfg, dev, ch, w,
                                     seq_len=SEQ, decode_max_len=64)
        return srv, (dev, ch, w)

    def test_prefill_chunk_rows(self, stub):
        srv, (dev, ch, w) = stub
        m = srv.models["lm"]
        store = m.store()
        full = candidate_rows_for(m.backend, store, 0.05, 1, False, False)
        chunk = prefill_chunk_rows_for(m.backend, store, 0.05, 1,
                                       chunk_tokens=SEQ // 4,
                                       need_bytes=False)
        assert chunk.o1.shape == full.o1.shape
        assert np.all(np.diff(chunk.o1) >= 0)
        # dense MAC terms are linear in sequence length, the attention
        # term quadratic: n standalone chunk rows lower-bound the
        # monolithic row (the gap is the cross-chunk attention the
        # chunk-local specs cannot see) and stay within the dense-
        # dominated ballpark
        assert np.all(4 * chunk.o1[1:] <= full.o1[1:])
        assert np.all(4 * chunk.o1[1:] >= 0.9 * full.o1[1:])
        assert np.all(4 * chunk.o2[:-1] <= full.o2[:-1])
        with pytest.raises(ValueError, match=">= 2"):
            prefill_chunk_rows_for(m.backend, store, 0.05, 1,
                                   chunk_tokens=1, need_bytes=False)

    def test_ledger_pools_acceptance(self):
        led = CalibrationLedger()
        assert led.mean_accept_rate is None

        class _Dep:
            pass

        for prop, acc in ((4, 2), (6, 6)):
            led.accept_samples.append((float(prop), float(acc)))
        assert led.mean_accept_rate == pytest.approx(8 / 10)

    def test_record_decode_feeds_acceptance(self, lm):
        """The full loop: Deployment.generate with drafts on →
        record_decode → pooled mean_accept_rate → fit() pins it on the
        CalibratedCost the fleet engine resolves its default from."""
        cfg, params = lm
        srv = QPARTServer()
        backend = TransformerBackend(cfg, params, seq_len=SEQ,
                                     decode_max_len=MAX_LEN)
        toks = np.asarray(jax.random.randint(KEY, (8, SEQ), 0,
                                             cfg.vocab_size))
        srv.register("lm", backend, toks, np.zeros(8, np.int32))
        m = srv.models["lm"]
        L = cfg.num_layers
        m.s_w, m.s_x, m.rho = (np.ones(L), np.ones(L), np.full(L, 0.1))
        m.delta_table = {a: a * 50 for a in srv.levels}
        dev = DeviceProfile(memory_bytes=2e9)
        ch = Channel(capacity_bps=2e6)
        w = ObjectiveWeights()
        srv.build_store("lm", dev, ch, w)
        dep = srv.serve(InferenceRequest("lm", 0.05, dev, ch, w))
        out = dep.generate(np.zeros((1, 8), np.int32), 6, draft_tokens=2)
        meas = dep.result.extra["measured_decode"]
        assert meas["draft_tokens"] == 2
        assert meas["accept_rate"] == out.accept_rate is not None
        srv.record_decode(dep)
        assert srv.ledger.mean_accept_rate == out.accept_rate
        fit = srv.ledger.fit()
        if fit is not None:
            assert fit.mean_accept_rate == out.accept_rate


class TestFleetSpecChunk:
    def _stub(self, server=None, cap=2e6):
        cfg = _f32(get_config("smollm-135m").reduced())
        dev = DeviceProfile(memory_bytes=2e9)
        ch = Channel(capacity_bps=cap)
        w = ObjectiveWeights()
        srv = QPARTServer(server)
        stub_transformer_calibration(srv, "lm", cfg, dev, ch, w,
                                     seq_len=SEQ, decode_max_len=64)
        return srv, (dev, ch, w)

    def _reqs(self, dev, ch, w, n=5, **kw):
        return [InferenceRequest("lm", 0.05, dev, ch, w, arrival_time=0.0,
                                 device_id=f"d{i}", max_new_tokens=20,
                                 **kw)
                for i in range(n)]

    def test_zero_knob_engine_is_bitwise_pr9(self):
        """Explicit default knobs journal EXACTLY what the knob-less
        engine journals — header keys absent, every entry identical —
        and the journal replays."""
        srv, (dev, ch, w) = self._stub()
        reqs = self._reqs(dev, ch, w)
        m0 = FleetEngine(srv).run(reqs)
        m1 = FleetEngine(srv, draft_tokens=0, accept_rate=None,
                         prefill_chunk_tokens=None).run(reqs)
        assert m0.journal.diff(m1.journal) is None
        assert "draft_tokens" not in m0.journal.header
        assert "prefill_chunk_tokens" not in m0.journal.header
        m0.journal.verify_replay(srv, reqs)

    def test_chunked_lane_interleaves_and_replays(self):
        srv, (dev, ch, w) = self._stub()
        reqs = self._reqs(dev, ch, w)
        m = FleetEngine(srv, prefill_chunk_tokens=4).run(reqs)
        m.assert_terminal()
        chunks = [e for e in m.journal.entries
                  if e.kind == "prefill_chunk"]
        ran = [e for e in chunks if dict(e.data).get("stale") is False]
        assert ran, "no chunk rounds executed"
        assert any(dict(e.data).get("last") for e in ran)
        assert m.journal.header["prefill_chunk_tokens"] == 4
        m.journal.verify_replay(srv, reqs)

    def test_chunked_single_request_ttft_pipelines(self):
        """With no lane contention, chunked prefill overlaps transfer
        with server compute: TTFT strictly below the monolithic
        ship→transfer→serve sum."""
        srv, (dev, ch, w) = self._stub()
        req = self._reqs(dev, ch, w, n=1)
        mono = FleetEngine(srv).run(req)
        chnk = FleetEngine(srv, prefill_chunk_tokens=4).run(req)
        assert chnk.records[0].ttft < mono.records[0].ttft
        assert chnk.records[0].tokens_emitted == \
            mono.records[0].tokens_emitted

    def _slow(self):
        """Device-favoring fleet: a slow server pushes the planner to
        p > 0 (the regime where drafting has a round trip to amortize)."""
        slow = ServerProfile(f_clock=1e7)
        srv, (dev, ch, w) = self._stub(server=slow, cap=200e6)
        return srv, slow, (dev, ch, w)

    def test_spec_rounds_amortize_and_replay(self):
        srv, slow, (dev, ch, w) = self._slow()
        reqs = self._reqs(dev, ch, w, n=4)
        m0 = FleetEngine(srv, servers=[slow]).run(reqs)
        assert m0.records[0].deployment.plan.p > 0

        def _rounds(m):
            return sum(1 for e in m.journal.entries
                       if e.kind == "decode_step"
                       and not dict(e.data)["stale"])

        m1 = FleetEngine(srv, servers=[slow], draft_tokens=3,
                         accept_rate=0.8).run(reqs)
        m1.assert_terminal()
        assert _rounds(m1) < _rounds(m0)
        for r0, r1 in zip(m0.records, m1.records):
            assert r1.tokens_emitted == r0.tokens_emitted
        assert m1.journal.header["draft_tokens"] == 3
        assert m1.journal.header["accept_rate"] == 0.8
        m1.journal.verify_replay(srv, reqs, servers=[slow])

    def test_spec_emission_is_deterministic_expected_rate(self):
        """The fractional-accumulator emission hits E[1 + α·k] exactly
        over the stream (no RNG): a 20-token stream at k=3, α=0.8 takes
        ceil(19 / 3.4) + ... rounds — just assert the journaled per-
        round emissions sum to the stream lengths and never exceed
        k + 1."""
        srv, slow, (dev, ch, w) = self._slow()
        reqs = self._reqs(dev, ch, w, n=2)
        m = FleetEngine(srv, servers=[slow], draft_tokens=3,
                        accept_rate=0.8).run(reqs)
        emitted = [dict(e.data)["emitted"]
                   for e in m.journal.entries
                   if e.kind == "decode_step"
                   and not dict(e.data)["stale"]]
        assert emitted and all(
            1 <= v <= 4 for row in emitted for v in row)
        total = sum(v for row in emitted for v in row)
        assert total == sum(r.tokens_emitted - 1 for r in m.records)

    def test_chaos_both_knobs_severs_and_replays(self):
        srv, slow, (dev, ch, w) = self._slow()
        reqs = self._reqs(dev, ch, w, n=4)
        base = FleetEngine(srv, servers=[slow]).run(reqs)
        faults = [FaultEvent(base.horizon / 4, DISCONNECT, "d0"),
                  FaultEvent(base.horizon, RECONNECT, "d0")]
        m = FleetEngine(srv, servers=[slow], draft_tokens=2,
                        accept_rate=0.6, prefill_chunk_tokens=4,
                        faults=faults).run(reqs)
        m.assert_terminal()
        assert not m.dead_letters
        assert sum(int(r.faults) for r in m.records) >= 1
        m.journal.verify_replay(srv, reqs, servers=[slow])

    def test_engine_knob_validation(self):
        srv, _ = self._stub()
        with pytest.raises(ValueError, match="draft_tokens"):
            FleetEngine(srv, draft_tokens=-1)
        with pytest.raises(ValueError, match="accept_rate"):
            FleetEngine(srv, draft_tokens=2, accept_rate=1.5)
        with pytest.raises(ValueError, match="prefill_chunk_tokens"):
            FleetEngine(srv, prefill_chunk_tokens=1)
