"""moe_roofline — kernels (``kernels/moe_gmm.py``: the grouped
dequantize-fused expert matmul of the routed-expert layer, named
``moe_gmm``).

Sum over the traced requests of the least time the chip could take for
the kernel's useful work, divided by the kernel's summed device time in
the window, in %. The useful work of a request comes from the program's
own counters, read once at the request's end inside a ``qpart.moe``
span (``serving/decode/pipeline.py``): per layer, the token-expert pairs
that landed on held experts (``pairs_here_by_layer``, M_l) and the held
experts that got at least one, summed over the request's dispatches
(``experts_active_by_layer``, A_l). For each expert layer l below the
plan's cut (``moe_layers(dims)``) and each of one expert's three matmuls
(``expert_matmuls(dims)``: (K, N) of gate, up and down), at the layer's
deployed bits b_l:
  operations 2*M_l*K*N; bytes A_l*K*N*b_l/8 (the active experts'
  weights, as ``plan_memory_bytes`` counts them) + 8*A_l (their
  per-tensor scale and zero) + 2*M_l*K + 2*M_l*N (bf16 in and out);
  the least time is the larger of operations over the bf16 peak and
  bytes over the HBM bandwidth (``bench/core/peaks.py``).
Summing the operations and bytes of a layer's calls before taking the
larger can only lower the least time, so the share stays a lower bound.

The kernel is told apart by its name: its calls carry
``kernel_metadata={"kernel":"moe_gmm"}`` in the trace's op text, which
the dense ``qmatmul``/``qmatmul4`` calls (``kernel_metadata={}``) do
not. ``qmatmul_roofline.is_kernel`` takes any TPU custom call with a
uint8 operand and so would count this kernel too; it is not read in
the cells that run it. Moves ``itl_p95_ms``. None where the trace holds
no such kernel call or no ``qpart.moe`` span (a program without them).
"""
from __future__ import annotations

from bench.core import program_trace
from bench.core.peaks import least_time_s
from bench.core.trace import device_trace

NAME = '"kernel":"moe_gmm"'


def is_kernel(name: str) -> bool:
    """A device op of the grouped expert kernel."""
    return 'custom_call_target="tpu_custom_call"' in name and NAME in name


def per_layer(span, key: str) -> list:
    return [int(v) for v in str(span.args[key]).split()]


def request_time_s(view, rec, span) -> float:
    """Least time of one request's kernel work (module docstring)."""
    plan = rec.plan
    if plan.p == 0 or max(plan.bits_w) > 8:
        return 0.0
    pairs = per_layer(span, "pairs_here_by_layer")
    active = per_layer(span, "experts_active_by_layer")
    t = 0.0
    for layer in view.family.moe_layers(view.dims):
        if layer >= plan.p:
            continue
        m, a, b = pairs[layer], active[layer], plan.bits_w[layer]
        flops = nbytes = 0.0
        for k, n in view.family.expert_matmuls(view.dims):
            flops += 2.0 * m * k * n
            nbytes += a * (k * n * b / 8.0 + 8.0) + 2.0 * m * (k + n)
        t += least_time_s(flops, nbytes, view.device_kind)
    return t


def owner(spans, t: int):
    """The bench span of ``spans`` open at host time ``t``, or None."""
    for s in spans:
        if s.start <= t < s.end:
            return s
    return None


def read(view):
    tv = device_trace(view)
    pv = program_trace.view_of(view)
    if tv is None or pv is None or not pv.of("moe"):
        return None
    kernel_ns = tv.op_ns(is_kernel)
    if kernel_ns == 0:
        return None
    recs = {r.index: r for r in view.records if r.plan is not None}
    gens = tv.of("generate")
    least = 0.0
    for span in pv.of("moe"):
        gen = owner(gens, span.start)
        if gen is not None and gen.args["request"] in recs:
            least += request_time_s(view, recs[gen.args["request"]], span)
    return 100.0 * least / (kernel_ns / 1e9)
