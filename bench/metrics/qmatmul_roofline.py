"""qmatmul_roofline — kernels (``kernels/qmatmul.py``: ``qmatmul_pallas``
and ``qmatmul4_pallas``, the dequantize-fused matmuls of the device
segment).

Sum over every call in the traced window of the least time the chip
could take for the call, divided by the summed device time of both
kernels, in %. The calls counted are the useful ones: the matmuls of
each device layer (layers below the cut) that the model family routes
through the kernels (``routed_matmuls(dims)`` in ``bench/families/
<family>.py``: (K, N) at published head counts), once for the prompt
(M = prompt tokens) and once per decode step (M = 1), for plans whose
bits are all <= 8 (the program serves any other plan with dense
weights). Work per call (M, K, N, b bits):
  operations 2*M*K*N; bytes K*N*b/8 (weights at the deployed bits, as
  ``plan_memory_bytes`` counts them) + 2*M*K + 2*M*N (bf16 in and out).
Moves ``itl_p95_ms``.
"""
from __future__ import annotations

from bench.core.trace import device_trace

from bench.core.peaks import least_time_s


def is_kernel(name: str) -> bool:
    """A Pallas call of either dequantize-fused matmul. The trace names a
    device op by its HLO text; these kernels are the TPU custom calls
    with a uint8 operand (the weight codes)."""
    if 'custom_call_target="tpu_custom_call"' not in name:
        return False
    operands = name.split("custom-call(", 1)[-1].split(
        "), custom_call_target", 1)[0]
    return any(op.startswith("u8[") for op in operands.split(", "))


def call_time_s(m: int, k: int, n: int, bits: int, kind: str) -> float:
    return least_time_s(2.0 * m * k * n,
                        k * n * bits / 8.0 + 2.0 * m * k + 2.0 * m * n, kind)


def request_time_s(view, rec) -> float:
    """Least time of one request's kernel calls; none where the plan
    deploys a layer above 8 bits, which the program serves with dense
    weights (the uint8 wire cannot carry it)."""
    plan = rec.plan
    t = 0.0
    if plan.p == 0 or max(plan.bits_w) > 8:
        return t
    shapes = view.family.routed_matmuls(view.dims)
    for layer in range(plan.p):
        b = plan.bits_w[layer]
        for k, n in shapes:
            t += call_time_s(rec.prompt_len, k, n, b, view.device_kind)
            t += (len(rec.token_times) - 1) * call_time_s(
                1, k, n, b, view.device_kind)
    return t


def read(view):
    tv = device_trace(view)
    if tv is None or not tv.of("generate"):
        return None
    kernel_ns = tv.op_ns(is_kernel)
    if kernel_ns == 0:
        return None
    traced = {s.args["request"] for s in tv.of("generate")}
    least = sum(request_time_s(view, r) for r in view.records
                if r.index in traced and r.plan is not None)
    return 100.0 * least / (kernel_ns / 1e9)
