"""Work counts of the per-layer metrics against hand counts at a tiny
size, with published heads and plan bits: the dense family's counts
(``bench/families/dense.py``) and the readers' least times built on
them."""
from __future__ import annotations

import types

import pytest

from bench.core import peaks
from bench.core.program import Plan
from bench.core.spec import family_module, metric_reader

TINY = {"L": 2, "D": 8, "H": 4, "KV": 2, "hd": 2, "F": 16, "V": 32}
KIND = "TPU v5 lite"
PK = peaks.PEAKS[KIND]
DENSE = family_module("dense")
VIEW = types.SimpleNamespace(dims=TINY, device_kind=KIND, family=DENSE)


def test_decode_flops_by_hand():
    # per layer: q 8*4*2=64, k,v 2*8*2*2=64, o 4*2*8=64, mlp 3*8*16=384
    per_layer = 64 + 64 + 64 + 384
    want = 2 * 2 * per_layer + 2 * 8 * 32 + 4 * 2 * 4 * 2 * 10
    assert DENSE.decode_flops(TINY, 10) == want


def test_qmatmul_shapes_and_call_by_hand():
    q = metric_reader("qmatmul_roofline")
    assert DENSE.routed_matmuls(TINY) == [(8, 8), (8, 4), (8, 4), (8, 8),
                                          (8, 16), (8, 16), (16, 8)]
    # M=1, K=8, N=16 at 5 bits: 256 flops; 8*16*5/8 + 2*8 + 2*16 bytes
    want = max(256 / PK["bf16_flops_per_s"],
               (80 + 16 + 32) / PK["hbm_bytes_per_s"])
    assert q.call_time_s(1, 8, 16, 5, KIND) == pytest.approx(want)


def test_qmatmul_request_counts_prefill_and_steps():
    q = metric_reader("qmatmul_roofline")
    rec = types.SimpleNamespace(plan=Plan(1, (4, 8), 8), prompt_len=3,
                                token_times=[0.0, 1.0, 2.0])
    want = sum(q.call_time_s(3, k, n, 4, KIND) + 2 * q.call_time_s(
        1, k, n, 4, KIND) for k, n in DENSE.routed_matmuls(TINY))
    assert q.request_time_s(VIEW, rec) == pytest.approx(want)


def test_qmatmul_skips_plans_served_dense():
    q = metric_reader("qmatmul_roofline")
    for plan in (Plan(2, (8, 9), 9), Plan(0, (), 0)):
        rec = types.SimpleNamespace(plan=plan, prompt_len=3,
                                    token_times=[0.0, 1.0])
        assert q.request_time_s(VIEW, rec) == 0.0


def test_decode_attention_step_by_hand():
    a = metric_reader("decode_attn_roofline")
    plan = Plan(1, (4, 4), 8)          # layer 0 on the device (float8)
    c = 10
    dev = max(4 * 4 * 2 * c / PK["bf16_flops_per_s"],
              (2 * c * 2 * 2 * 1 + 4 * 4 * 2) / PK["hbm_bytes_per_s"])
    srv = max(4 * 4 * 2 * c / PK["bf16_flops_per_s"],
              (2 * c * 2 * 2 * 2 + 4 * 4 * 2) / PK["hbm_bytes_per_s"])
    assert a.step_time_s(VIEW, plan, c) == pytest.approx(dev + srv)
    offload = Plan(0, (), 0)
    assert a.step_time_s(VIEW, offload, c) == pytest.approx(2 * srv)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
