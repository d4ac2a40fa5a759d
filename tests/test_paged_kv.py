"""Page-granular KV pricing (DESIGN.md §13): a stream's context is
priced rounded up to the page, never above the dense worst case;
page-rounded admission admits stream configs the ``decode_max_len``
worst-case mask rejects; and the fleet's ``PageLedger`` leaks nothing
over a seeded trace with mid-stream disconnects."""
import dataclasses

import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.cost_model import Channel, DeviceProfile, ObjectiveWeights
from repro.serving.decode.cache import PageLedger, paged_kv_ctx
from repro.serving.engine import FleetEngine
from repro.serving.engine.faults import DISCONNECT, RECONNECT, FaultEvent
from repro.serving.pricing import price_window
from repro.serving.qpart_server import QPARTServer
from repro.serving.simulator import InferenceRequest
from repro.serving.testing import stub_transformer_calibration

pytestmark = pytest.mark.smoke

SEQ = 16


class TestPagedKVCtx:
    def test_rounds_up_to_page_and_caps_at_max(self):
        assert paged_kv_ctx(1, 16, 64) == 16
        assert paged_kv_ctx(16, 16, 64) == 16
        assert paged_kv_ctx(17, 16, 64) == 32
        assert paged_kv_ctx(1000, 16, 64) == 64

    def test_never_exceeds_dense_worst_case(self):
        for tokens in range(1, 200, 7):
            assert paged_kv_ctx(tokens, 16, 64) <= 64

    def test_monotone_in_tokens(self):
        ctxs = [paged_kv_ctx(t, 16, 64) for t in range(1, 128)]
        assert all(a <= b for a, b in zip(ctxs, ctxs[1:]))


class TestPagedAdmission:
    """kv_bytes_row(tokens=...) + the pricing/serve masks: page-rounded
    actual context admits what the worst-case bound rejects."""

    def _server(self, kv_page_tokens, memory_bytes):
        cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                                  dtype="float32")
        dev = DeviceProfile(memory_bytes=memory_bytes)
        ch = Channel(capacity_bps=2e6)
        w = ObjectiveWeights()
        srv = QPARTServer()
        stub_transformer_calibration(srv, "lm", cfg, dev, ch, w,
                                     seq_len=SEQ, decode_max_len=512,
                                     kv_page_tokens=kv_page_tokens)
        return srv, (dev, ch, w)

    def test_row_paged_leq_dense_and_monotone(self):
        srv, _ = self._server(16, 2e9)
        be = srv.models["lm"].backend
        dense = be.kv_bytes_row(1)
        short = be.kv_bytes_row(1, tokens=SEQ + 4)
        longer = be.kv_bytes_row(1, tokens=SEQ + 200)
        assert np.all(short <= dense) and np.all(longer <= dense)
        assert np.all(short <= longer)
        assert short[-1] < dense[-1]     # short stream strictly cheaper
        # page rounding: +1 token inside the same page is free
        same = be.kv_bytes_row(1, tokens=SEQ + 5)
        np.testing.assert_array_equal(short, same)

    def _device_memory_between(self):
        """A budget that fits weights + paged KV of a short stream but
        NOT weights + the 512-token worst case, for some cut."""
        srv, _ = self._server(16, 2e9)
        m = srv.models["lm"]
        store = m.store(None)
        lvl = store.level_for(0.05)
        mem = store.level_memory_rows(lvl)
        dense = m.backend.kv_bytes_row(1)
        paged = m.backend.kv_bytes_row(1, tokens=SEQ + 4)
        need_dense = np.asarray(mem) + np.asarray(dense)
        need_paged = np.asarray(mem) + np.asarray(paged)
        # pick a budget between the two for the LAST cut
        c = len(dense) - 1
        assert need_paged[c] < need_dense[c]
        return float((need_paged[c] + need_dense[c]) / 2)

    def test_mask_admits_config_dense_rejects(self):
        """The acceptance criterion: at a device-memory budget BETWEEN
        the paged and worst-case requirements, the ``price_window``
        admission mask rejects the deep cut under dense reservation and
        admits it under page-rounded pricing."""
        budget = self._device_memory_between()
        srv_d, (dev, ch, w) = self._server(None, budget)
        srv_p, _ = self._server(16, budget)
        dev = dataclasses.replace(dev, memory_bytes=budget)
        req = InferenceRequest("lm", 0.05, dev, ch, w, max_new_tokens=4)
        tab_d = price_window(srv_d.models, srv_d.server, [req])
        tab_p = price_window(srv_p.models, srv_p.server, [req])
        c = len(tab_d.obj[0]) - 1                    # the deepest cut
        assert np.isinf(tab_d.obj[0][c]), \
            "worst-case mask should reject the deep cut"
        assert np.isfinite(tab_p.obj[0][c]), \
            "page-rounded mask should admit it"
        # the paged mask only ever widens the feasible set
        feas_d = np.isfinite(tab_d.obj[0])
        feas_p = np.isfinite(tab_p.obj[0])
        assert np.all(feas_p | ~feas_d), "paged must not reject what " \
            "dense admits"

    def test_serve_feasibility_uses_paged_row(self):
        """``QPARTServer.serve`` plans through the same widened mask —
        at the in-between budget the paged server can deploy the deep
        cut, the dense server cannot (its feasible_fn rejects it)."""
        budget = self._device_memory_between()
        srv_d, (dev, ch, w) = self._server(None, budget)
        srv_p, _ = self._server(16, budget)
        dev = dataclasses.replace(dev, memory_bytes=budget)
        req = InferenceRequest("lm", 0.05, dev, ch, w, max_new_tokens=4)
        # both serve successfully (p=0 is always feasible) ...
        p_dense = srv_d.serve(req).plan.p
        p_paged = srv_p.serve(req).plan.p
        L = srv_d.models["lm"].backend.num_layers
        assert p_dense < L
        # ... and the paged feasible set strictly contains the dense one
        kv_d = srv_d.models["lm"].backend.kv_bytes_row(req.batch)
        kv_p = srv_p.models["lm"].backend.kv_bytes_row(
            req.batch, tokens=SEQ + req.max_new_tokens)
        store = srv_d.models["lm"].store(None)
        mem = np.asarray(store.level_memory_rows(store.level_for(0.05)))
        assert mem[L] + kv_d[L] > budget >= mem[L] + kv_p[L]
        assert p_paged >= p_dense


class TestFleetLedger:
    def _stub(self, kv_page_tokens=16):
        cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                                  dtype="float32")
        # fast channel + expensive server compute: the objective argmin
        # lands on a device cut p > 0, so streams actually hold device KV
        dev = DeviceProfile(memory_bytes=2e9)
        ch = Channel(capacity_bps=2e10)
        w = ObjectiveWeights(eta=1e5)
        srv = QPARTServer()
        stub_transformer_calibration(srv, "lm", cfg, dev, ch, w,
                                     seq_len=SEQ, decode_max_len=64,
                                     kv_page_tokens=kv_page_tokens)
        return srv, (dev, ch, w)

    def test_no_leak_over_seeded_trace(self):
        srv, (dev, ch, w) = self._stub()
        reqs = [InferenceRequest("lm", 0.05, dev, ch, w, arrival_time=0.0,
                                 device_id=f"d{i}", max_new_tokens=20)
                for i in range(5)]
        eng = FleetEngine(srv)
        metrics = eng.run(reqs)
        metrics.assert_terminal()
        led = eng.kv_ledger
        assert led.open_streams == 0
        assert led.resident_bytes == 0
        assert led.total_page_allocs == led.total_page_frees > 0
        assert led.peak_bytes > 0

    def test_no_leak_through_midstream_severance(self):
        srv, (dev, ch, w) = self._stub()
        reqs = [InferenceRequest("lm", 0.05, dev, ch, w, arrival_time=0.0,
                                 device_id="d0", max_new_tokens=40),
                InferenceRequest("lm", 0.05, dev, ch, w, arrival_time=0.0,
                                 device_id="d1", max_new_tokens=40)]
        horizon = FleetEngine(srv).run(reqs).horizon
        faults = [FaultEvent(horizon / 2, DISCONNECT, "d0"),
                  FaultEvent(horizon, RECONNECT, "d0")]
        eng = FleetEngine(srv, faults=faults)
        metrics = eng.run(reqs)
        metrics.assert_terminal()
        assert metrics.records[0].faults == 1       # really severed
        led = eng.kv_ledger
        assert led.open_streams == 0
        assert led.resident_bytes == 0
        assert led.total_page_allocs == led.total_page_frees > 0

    def test_residency_grows_with_stream(self):
        led = PageLedger()
        led.open(0, 100.0, 2)
        led.grow(0, 150.0, 3)
        assert led.resident_bytes == 150.0 and led.resident_pages == 3
        led.grow(0, 140.0, 3)                        # never shrinks
        assert led.resident_bytes == 150.0
        assert led.peak_bytes == 150.0
        assert led.close(0) == 3
        assert led.open_streams == 0 and led.resident_bytes == 0

    def test_legacy_dense_backend_untouched(self):
        """Without kv_page_tokens the ledger stays empty — zero decode-
        lane overhead and bit-identical legacy behavior."""
        srv, (dev, ch, w) = self._stub(kv_page_tokens=None)
        reqs = [InferenceRequest("lm", 0.05, dev, ch, w, arrival_time=0.0,
                                 device_id="d0", max_new_tokens=10)]
        eng = FleetEngine(srv)
        eng.run(reqs).assert_terminal()
        assert eng.kv_ledger.total_page_allocs == 0
        assert eng.kv_ledger.peak_bytes == 0
