"""The reduction from a recorded chip trace to busy time, span
attribution and kernel time, on a small trace kept in ``data/``
(recorded by ``make_trace_fixture.py`` on one v5e)."""
from __future__ import annotations

import pathlib
import types

import numpy as np
import pytest

from bench.core import trace
from bench.core.spec import metric_reader

FIXTURE = pathlib.Path(__file__).parent / "data" / "chip_trace.xplane.pb"


@pytest.fixture(scope="module")
def tv():
    return trace.reduce(str(FIXTURE))


def test_merge_and_overlap():
    iv = trace.merge(np.array([[5, 9], [0, 3], [2, 4], [9, 10]]))
    np.testing.assert_array_equal(iv, [[0, 4], [5, 10]])
    assert trace.overlap_ns(iv, np.array([[3, 6], [8, 20]])) == 1 + 1 + 2


def test_clock_offset(tv):
    # three programs, each enqueued 1.316-1.329 ms (host clock) after
    # the device clock says it started: the largest difference aligns
    assert (tv.n_paired, tv.offset_ns) == (3, 1328750)


def test_spans_and_window(tv):
    assert tv.n_devices == 1
    for name in ("window", "serve", "generate", "prefill", "decode",
                 "arrival_wait"):
        assert len(tv.of(name)) == 1, name
    w0, w1 = tv.window
    for spans in tv.spans.values():
        for s in spans:
            assert w0 <= s.start <= s.end <= w1
    assert tv.of("decode")[0].args == {"request": 0, "step": 1}


def test_busy_is_inside_spans(tv):
    total = float((tv.busy[:, 1] - tv.busy[:, 0]).sum())
    assert total > 0
    parts = sum(tv.busy_ns([(s.start, s.end)]) for name in
                ("serve", "prefill", "decode", "arrival_wait")
                for s in tv.of(name))
    # every device op of the window ran inside one of the four spans
    assert parts == pytest.approx(total, rel=0.05)
    wait = tv.of("arrival_wait")[0]
    assert tv.busy_ns([(wait.start, wait.end)]) < 0.05 * (wait.end
                                                         - wait.start)


def test_kernels_are_found_where_they_ran(tv):
    q = metric_reader("qmatmul_roofline")
    a = metric_reader("decode_attn_roofline")
    pre = tv.of("prefill")[0]
    dec = tv.of("decode")[0]
    assert tv.op_ns(q.is_kernel) > 0
    assert tv.op_ns(q.is_kernel, [(pre.start, pre.end)]) == tv.op_ns(
        q.is_kernel)
    assert tv.op_ns(a.is_kernel, [(dec.start, dec.end)]) == tv.op_ns(
        a.is_kernel) > 0


def test_summary(tv):
    s = trace.device_summary(tv)
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["breakdown"]["idle_gaps"][0][0] == "arrival_wait"
    assert len(s["breakdown"]["device_ops"]) <= 10


def test_readers_on_the_fixture(tv):
    view = types.SimpleNamespace(trace=tv, records=[
        types.SimpleNamespace(index=0, prompt_len=8, serve_start=0.0,
                              serve_end=0.001)])
    assert metric_reader("prefill_us_per_token").read(view) > 0
    assert metric_reader("decode_step_ms").read(view) > 0
    idle = metric_reader("serve_idle_share").read(view)
    assert 0 < idle < 100
