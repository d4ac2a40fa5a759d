"""Autoregressive decode serving (DESIGN.md §11): segment prefill/decode
parity against the monolithic forward family (attention AND SSM blocks),
``DecodeSession`` greedy streams across cut points on ONE set of jitted
programs (compile-once), the KV-cache dtype/footprint contract for
quantized device segments, per-token pricing rows, KV-aware feasibility,
``Deployment.generate`` → ledger, and the fleet engine's continuous-
batching decode lane (metrics keys, chaos severance, replay)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.configs.classifier import MNIST_MLP
from repro.core.cost_model import Channel, DeviceProfile, ObjectiveWeights
from repro.core.solver import PartitionPlan
from repro.models import transformer as T
from repro.serving.backends import TransformerBackend
from repro.serving.decode import (DecodeSession, kv_cache_dtype,
                                  segment_cache_bytes)
from repro.serving.engine import FleetEngine
from repro.serving.engine.faults import (DISCONNECT, RECONNECT, FaultEvent)
from repro.serving.errors import ServingError
from repro.serving.pricing import decode_rows_for
from repro.serving.qpart_server import QPARTServer
from repro.serving.simulator import InferenceRequest
from repro.serving.testing import (stub_calibration,
                                   stub_transformer_calibration)

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


@pytest.fixture(scope="module", params=["smollm-135m", "mamba2-1.3b"],
                ids=["attn", "ssm"])
def family(request):
    cfg = _f32(get_config(request.param).reduced())
    return cfg, T.init_params(KEY, cfg)


@pytest.fixture(scope="module")
def lm():
    """Tiny trained-free smollm: untrained params are fine — parity is a
    numerical property, not an accuracy one."""
    cfg = dataclasses.replace(
        get_config("smollm-135m").reduced(), name="smollm-decode",
        d_model=64, num_heads=2, num_kv_heads=1, head_dim=32, d_ff=128,
        vocab_size=32, tp_pad=1, dtype="float32")
    return cfg, T.init_params(KEY, cfg)


@pytest.fixture(scope="module")
def ssm():
    cfg = _f32(get_config("mamba2-1.3b").reduced())
    return cfg, T.init_params(KEY, cfg)


class TestSegmentParity:
    """segment_prefill / segment_decode_step == the monolithic prefill /
    decode_step, for both block families, bit for bit."""

    def test_full_segment_prefill_matches_prefill(self, family):
        cfg, params = family
        toks = jax.random.randint(KEY, (2, SEQ), 0, cfg.vocab_size)
        lg_ref, caches_ref, _ = T.prefill(params, cfg, toks, max_len=MAX_LEN,
                                          cache_dtype=jnp.float32)
        h0 = T.embed_tokens(params, cfg, toks)
        cache0 = T.init_cache(cfg, 2, MAX_LEN, jnp.float32)
        h, caches = T.segment_prefill(params, cfg, h0, cache0, 0,
                                      cfg.num_layers)
        lg = T.unembed(params, cfg, h)
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(lg_ref))
        for a, b in zip(jax.tree.leaves(caches),
                        jax.tree.leaves(caches_ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_split_prefill_matches_monolithic(self, family):
        cfg, params = family
        L = cfg.num_layers
        p = L // 2
        toks = jax.random.randint(KEY, (2, SEQ), 0, cfg.vocab_size)
        h0 = T.embed_tokens(params, cfg, toks)
        cache0 = T.init_cache(cfg, 2, MAX_LEN, jnp.float32)
        h_ref, _ = T.segment_prefill(params, cfg, h0, cache0, 0, L)
        h_dev, _ = T.segment_prefill(params, cfg, h0,
                                     T.init_cache(cfg, 2, MAX_LEN,
                                                  jnp.float32), 0, p)
        h_srv, _ = T.segment_prefill(params, cfg, h_dev,
                                     T.init_cache(cfg, 2, MAX_LEN,
                                                  jnp.float32), p, L)
        np.testing.assert_array_equal(np.asarray(h_srv), np.asarray(h_ref))

    def test_segment_decode_step_matches_decode_step(self, family):
        cfg, params = family
        toks = jax.random.randint(KEY, (2, SEQ), 0, cfg.vocab_size)
        lg, caches, _ = T.prefill(params, cfg, toks, max_len=MAX_LEN,
                                  cache_dtype=jnp.float32)
        nxt = jnp.argmax(lg[:, -1:], -1).astype(jnp.int32)
        pos = jnp.asarray(SEQ, jnp.int32)
        lg_ref, _ = T.decode_step(params, cfg, nxt, caches, pos)
        x = T.embed_tokens(params, cfg, nxt)
        x_out, _ = T.segment_decode_step(params, cfg, x, caches, pos, 0,
                                         cfg.num_layers)
        lg_seg = T.unembed(params, cfg, x_out)
        np.testing.assert_array_equal(np.asarray(lg_seg[:, 0]),
                                      np.asarray(lg_ref[:, 0]))


class TestDecodeSession:
    def _greedy_reference(self, cfg, params, prompt, n):
        """Teacher-forced greedy reference via the full forward."""
        toks = jnp.asarray(prompt, jnp.int32)
        out = []
        for _ in range(n):
            lg, _ = T.forward(params, cfg, toks)
            nxt = jnp.argmax(lg[:, -1:], -1).astype(jnp.int32)
            out.append(np.asarray(nxt[:, 0]))
            toks = jnp.concatenate([toks, nxt], axis=1)
        return np.stack(out, axis=1)

    def test_full_offload_matches_forward_greedy(self, lm):
        cfg, params = lm
        backend = TransformerBackend(cfg, params, seq_len=SEQ)
        prompt = jax.random.randint(KEY, (2, SEQ), 0, cfg.vocab_size)
        sess = DecodeSession(backend, _manual_plan(0), max_len=MAX_LEN)
        out = sess.generate(prompt, 6)
        ref = self._greedy_reference(cfg, params, prompt, 6)
        np.testing.assert_array_equal(out.tokens, ref)
        assert out.ttft_s > 0 and len(out.per_token_s) == 5
        assert out.device_cache_bytes == 0          # nothing resides on-device

    def test_cuts_agree_and_compile_once(self, lm):
        """Every cut point produces the p=0 greedy stream at fp bit-
        widths, on a CONSTANT jitted-program count after the first cut
        (dynamic (start, stop, pos) — the compile-once contract). The
        unembed program is traced once for every cut, device and server
        trees alike; only p = L skips its (empty) server segment."""
        cfg, params = lm
        backend = TransformerBackend(cfg, params, seq_len=SEQ)
        prompt = jax.random.randint(KEY, (2, SEQ), 0, cfg.vocab_size)
        L = cfg.num_layers
        ref = DecodeSession(backend, _manual_plan(0),
                            max_len=MAX_LEN).generate(prompt, 6).tokens
        first_cut = DecodeSession(backend, _manual_plan(1),
                                  max_len=MAX_LEN).generate(prompt, 6)
        np.testing.assert_array_equal(first_cut.tokens, ref)
        traces = backend.trace_count
        for p in (L // 2, L):
            assert backend.counters["segment.skip"] == 0
            out = DecodeSession(backend, _manual_plan(p),
                                max_len=MAX_LEN).generate(prompt, 6)
            np.testing.assert_array_equal(out.tokens, ref)
        assert backend.trace_count == traces, \
            "decode programs re-traced across cut points"
        assert backend.counters["trace.unembed"] == 1
        assert "trace.h_logits" not in backend.counters
        # p = L: the prefill and each of the 5 steps skip [L, L)
        assert backend.counters["segment.skip"] == 6
        assert out.server_cache_bytes == 0

    @pytest.mark.parametrize("cut", ["0", "1", "L"])
    def test_ssm_cuts_agree_and_compile_once(self, ssm, cut):
        """The prefill of a stack the extend program cannot admit (SSM
        state is a running reduction) streams the p = 0 greedy tokens at
        every cut, on a constant jitted-program count after the first."""
        cfg, params = ssm
        backend = TransformerBackend(cfg, params, seq_len=SEQ)
        prompt = jax.random.randint(KEY, (2, SEQ), 0, cfg.vocab_size)
        L = cfg.num_layers
        p = L if cut == "L" else int(cut)
        ref = DecodeSession(backend, _manual_plan(0),
                            max_len=MAX_LEN).generate(prompt, 6).tokens
        first_cut = DecodeSession(backend, _manual_plan(1),
                                  max_len=MAX_LEN).generate(prompt, 6)
        np.testing.assert_array_equal(first_cut.tokens, ref)
        traces = backend.trace_count
        out = DecodeSession(backend, _manual_plan(p),
                            max_len=MAX_LEN).generate(prompt, 6)
        np.testing.assert_array_equal(out.tokens, ref)
        assert backend.trace_count == traces
        assert "trace.extend_seg" not in backend.counters
        assert backend.counters["segment.skip"] == (6 if p == L else 0)

    def test_quantized_cache_dtype_and_footprint(self, lm):
        """Satellite 4: a quantized device segment holds its KV cache in
        the deployed bit-width's storage dtype — 8-bit → float8 at HALF
        the bf16 footprint, no silent upcast to the model dtype."""
        cfg, params = lm
        backend = TransformerBackend(cfg, params, seq_len=SEQ)
        prompt = jax.random.randint(KEY, (2, SEQ), 0, cfg.vocab_size)
        p = cfg.num_layers // 2
        lo = DecodeSession(backend, _manual_plan(p, bits=8.0),
                           max_len=MAX_LEN)
        hi = DecodeSession(backend, _manual_plan(p, bits=16.0),
                           max_len=MAX_LEN)
        assert lo.dev_dtype == jnp.float8_e4m3fn
        assert hi.dev_dtype == jnp.bfloat16
        out_lo = lo.generate(prompt, 4)
        out_hi = hi.generate(prompt, 4)
        assert out_lo.device_cache_dtype == "float8_e4m3fn"
        # footprint assertion: every device-cache leaf really is stored
        # at the narrow dtype (nbytes halves vs the bf16 cache)
        assert out_lo.device_cache_bytes * 2 == out_hi.device_cache_bytes
        for leaf in jax.tree.leaves(lo.dev_caches):
            assert leaf.dtype in (jnp.float8_e4m3fn, jnp.float32), leaf.dtype
        # tokens stay valid ids (low-bit streams may diverge from fp)
        assert out_lo.tokens.min() >= 0
        assert out_lo.tokens.max() < cfg.vocab_size

    def test_dtype_ladder(self):
        assert kv_cache_dtype(6) == jnp.float8_e4m3fn
        assert kv_cache_dtype(8) == jnp.float8_e4m3fn
        assert kv_cache_dtype(12) == jnp.bfloat16
        assert kv_cache_dtype(16) == jnp.bfloat16
        assert kv_cache_dtype(32, jnp.float32) == jnp.float32
        assert kv_cache_dtype(0, jnp.float32) == jnp.float32

    def test_prompt_overflow_raises(self, lm):
        cfg, params = lm
        backend = TransformerBackend(cfg, params, seq_len=SEQ)
        sess = DecodeSession(backend, _manual_plan(0), max_len=SEQ)
        prompt = jnp.zeros((1, SEQ), jnp.int32)
        with pytest.raises(ServingError, match="no room"):
            sess.prefill(prompt)


class TestDecodePricing:
    def _server(self, decode_max_len=64):
        cfg = _f32(get_config("smollm-135m").reduced())
        dev = DeviceProfile(memory_bytes=2e9)
        ch = Channel(capacity_bps=2e6)
        w = ObjectiveWeights()
        srv = QPARTServer()
        stub_transformer_calibration(srv, "lm", cfg, dev, ch, w,
                                     seq_len=SEQ,
                                     decode_max_len=decode_max_len)
        return srv, cfg, (dev, ch, w)

    def test_decode_rows_shape_and_monotonicity(self):
        srv, cfg, (dev, ch, w) = self._server()
        m = srv.models["lm"]
        rows = decode_rows_for(m.backend, m.store(None), m.store(None)
                               .level_for(0.05), 1, need_bytes=True)
        L = cfg.num_layers
        assert rows.o1.shape == (L + 1,)
        assert np.all(np.diff(rows.o1) > 0)          # per-token MACs cumulate
        assert np.all(np.diff(rows.o2) < 0)
        assert rows.dev_bytes is not None and rows.srv_bytes is not None
        # decode KV traffic scales with context, so per-token device
        # bytes dwarf the per-token MAC count's naive 2-byte estimate
        assert rows.dev_bytes[L] > 0

    def test_kv_footprint_prunes_candidates(self):
        """A decode-planned backend adds the max_len KV footprint to the
        feasibility mask: a device that fits the quantized weights but
        NOT weights + cache must fall back to smaller p / full offload."""
        srv, cfg, (dev, ch, w) = self._server(decode_max_len=64)
        kv_row = srv.models["lm"].backend.kv_bytes_row(1)
        assert kv_row is not None and kv_row[-1] > 0
        store = srv.models["lm"].store(None)
        lv = store.level_for(0.05)
        mem = store.level_memory_rows(lv)
        # budget that admits every candidate's WEIGHTS but not the full
        # cache at the deepest cuts
        budget = float(mem[-1]) + float(kv_row[-1]) * 0.5
        tight = dataclasses.replace(dev, memory_bytes=budget)
        dep = srv.serve(InferenceRequest("lm", 0.05, tight, ch, w))
        assert dep.plan.device_memory_bytes + kv_row[dep.plan.p] <= budget
        infeasible = [p for p in range(cfg.num_layers + 1)
                      if float(mem[p]) + float(kv_row[p]) > budget]
        assert dep.plan.p not in infeasible and infeasible

    def test_prefill_only_pricing_unchanged(self):
        """decode_max_len=None backends price bit-identically to the
        pre-decode engine: kv_bytes_row is None, no mask change."""
        srv, cfg, (dev, ch, w) = self._server(decode_max_len=None)
        assert srv.models["lm"].backend.kv_bytes_row(1) is None
        dep = srv.serve(InferenceRequest("lm", 0.05, dev, ch, w))
        assert dep.plan.objective > 0


class TestDeploymentGenerate:
    @pytest.fixture(scope="class")
    def served(self, lm):
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
        return srv, (dev, ch, w)

    def test_generate_streams_and_feeds_ledger(self, served):
        srv, (dev, ch, w) = served
        dep = srv.serve(InferenceRequest("lm", 0.05, dev, ch, w))
        seen = []
        prompt = np.zeros((1, 8), np.int32)
        out = dep.generate(prompt, 5, stream_cb=lambda i, t: seen.append(i))
        assert seen == [0, 1, 2, 3, 4]
        assert out.tokens.shape == (1, 5)
        meas = dep.result.extra["measured_decode"]
        assert meas["new_tokens"] == 5 and meas["tokens_per_s"] > 0
        n0 = len(srv.ledger.samples)
        srv.record_decode(dep)
        assert len(srv.ledger.samples) == n0 + 1

    def test_session_rejects_classifier_backend(self):
        from repro.models.classifier import init_classifier
        from repro.serving.backends import ClassifierBackend
        params = init_classifier(KEY, MNIST_MLP)
        backend = ClassifierBackend(MNIST_MLP, params)
        with pytest.raises(ServingError, match="decode"):
            DecodeSession(backend, _manual_plan(0), max_len=8)


class TestFleetDecode:
    def _stub(self, decode_max_len=64):
        cfg = _f32(get_config("smollm-135m").reduced())
        dev = DeviceProfile(memory_bytes=2e9)
        ch = Channel(capacity_bps=2e6)
        w = ObjectiveWeights()
        srv = QPARTServer()
        stub_transformer_calibration(srv, "lm", cfg, dev, ch, w,
                                     seq_len=SEQ,
                                     decode_max_len=decode_max_len)
        return srv, (dev, ch, w)

    def test_streams_complete_with_metrics(self):
        srv, (dev, ch, w) = self._stub()
        reqs = [InferenceRequest("lm", 0.05, dev, ch, w,
                                 arrival_time=0.0, device_id=f"d{i}",
                                 max_new_tokens=30) for i in range(6)]
        reqs.append(InferenceRequest("lm", 0.05, dev, ch, w,
                                     arrival_time=0.01, device_id="d9"))
        metrics = FleetEngine(srv).run(reqs)
        metrics.assert_terminal()
        s = metrics.summary()
        assert s["completed"] == 7
        assert s["tokens_per_s"] > 0
        assert s["ttft_p50"] is not None and s["ttft_p99"] >= s["ttft_p50"]
        for r in metrics.records[:6]:
            assert r.tokens_emitted == 30
            assert r.decode_done is not None
            assert r.latency > r.ttft          # the stream outlives TTFT
        assert metrics.records[6].decode_tokens == 0
        # decode rounds really batched: fewer rounds than request-tokens
        rounds = [e for e in metrics.journal.entries
                  if e.kind == "decode_step" and not dict(e.data)["stale"]]
        assert rounds and any(dict(e.data)["batch"] > 1 for e in rounds)
        metrics.journal.verify_replay(srv, reqs)

    def test_midstream_disconnect_severs_and_retries(self):
        srv, (dev, ch, w) = self._stub()
        reqs = [InferenceRequest("lm", 0.05, dev, ch, w, arrival_time=0.0,
                                 device_id="d0", max_new_tokens=50),
                InferenceRequest("lm", 0.05, dev, ch, w, arrival_time=0.0,
                                 device_id="d1", max_new_tokens=50)]
        horizon = FleetEngine(srv).run(reqs).horizon
        faults = [FaultEvent(horizon / 2, DISCONNECT, "d0"),
                  FaultEvent(horizon, RECONNECT, "d0")]
        metrics = FleetEngine(srv, faults=faults).run(reqs)
        metrics.assert_terminal()
        r0 = metrics.records[0]
        assert r0.faults == 1 and r0.attempts == 2 and not r0.rejected
        assert r0.tokens_emitted == r0.decode_tokens == 50
        assert metrics.records[1].faults == 0
        assert metrics.records[1].tokens_emitted == 50
        metrics.journal.verify_replay(srv, reqs)

    def test_decode_on_classifier_raises(self):
        srv = QPARTServer()
        dev = DeviceProfile()
        ch = Channel(capacity_bps=2e6)
        w = ObjectiveWeights()
        stub_calibration(srv, "clf", MNIST_MLP, dev, ch, w)
        req = InferenceRequest("clf", 0.05, dev, ch, w, max_new_tokens=4)
        with pytest.raises(ServingError, match="decode"):
            FleetEngine(srv).run([req])

    def test_zero_decode_trace_bit_identical(self):
        """max_new_tokens=0 traces through a decode-planned backend are
        decode-lane-free: no DECODE_STEP entries, zeroed decode metrics."""
        srv, (dev, ch, w) = self._stub()
        reqs = [InferenceRequest("lm", 0.05, dev, ch, w,
                                 arrival_time=0.02 * i, device_id=f"d{i}")
                for i in range(4)]
        metrics = FleetEngine(srv).run(reqs)
        metrics.assert_terminal()
        assert all(e.kind != "decode_step" for e in metrics.journal.entries)
        s = metrics.summary()
        assert s["tokens_per_s"] == 0.0
        assert all(r.decode_tokens == 0 for r in metrics.records)


class TestDecodeBatcher:
    """Observable semantics of the heap-backed continuous-batching state
    (PR 9) — locked against the pre-heap linear-scan implementation:
    ``due`` yields joiners in ADMISSION order, ``next_time`` is
    ``max(busy_until, min live ready_at)``, re-arms never reorder."""

    @staticmethod
    def _stream(index, ready_at):
        from repro.serving.decode.batching import DecodeStream
        return DecodeStream(index=index, token=(index, 1), device_id=None,
                            remaining=4, ready_at=ready_at, o2_tok=1.0,
                            srv_bytes_tok=1.0, step_lag=0.1)

    def _batcher(self):
        from repro.serving.decode.batching import DecodeBatcher
        return DecodeBatcher()

    def test_due_admission_order(self):
        b = self._batcher()
        for i, r in [(9, 0.5), (1, 0.2), (5, 0.9)]:
            b.add(self._stream(i, r))
        assert [s.index for s in b.due(1.0)] == [9, 1, 5]
        assert [s.index for s in b.due(0.3)] == [1]
        assert [s.index for s in b.due(0.6)] == [9, 1]

    def test_rearm_keeps_admission_order(self):
        b = self._batcher()
        b.add(self._stream(1, 0.0))
        b.add(self._stream(2, 0.0))
        b.rearm(1, 5.0)                      # later ready, same seat
        assert [s.index for s in b.due(10.0)] == [1, 2]
        assert b.streams[1].ready_at == 5.0
        assert [s.index for s in b.due(1.0)] == [2]

    def test_next_time_max_of_busy_and_min_ready(self):
        b = self._batcher()
        assert b.next_time() is None
        b.add(self._stream(1, 3.0))
        b.add(self._stream(2, 7.0))
        assert b.next_time() == 3.0
        b.busy_until = 4.5
        assert b.next_time() == 4.5
        b.rearm(1, 9.0)                      # stale heap top is skipped
        assert b.next_time() == 7.0

    def test_remove_then_readmit_enters_at_back(self):
        b = self._batcher()
        for i in (1, 2, 3):
            b.add(self._stream(i, 0.0))
        b.remove(1)
        assert [s.index for s in b.due(1.0)] == [2, 3]
        b.add(self._stream(1, 0.0))          # fresh admission → back
        assert [s.index for s in b.due(1.0)] == [2, 3, 1]

    def test_overwrite_add_keeps_seat(self):
        b = self._batcher()
        b.add(self._stream(1, 0.0))
        b.add(self._stream(2, 0.0))
        b.add(self._stream(1, 0.4))          # retry overwrite, same seat
        assert [s.index for s in b.due(1.0)] == [1, 2]
        assert b.streams[1].ready_at == 0.4

    def test_remove_clears_next_time(self):
        b = self._batcher()
        b.add(self._stream(1, 2.0))
        b.remove(1)
        assert b.next_time() is None
        assert b.due(10.0) == []
