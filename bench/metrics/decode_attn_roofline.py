"""decode_attn_roofline — kernels (``kernels/decode_attention.py``, the
single-query flash attention of every decode step).

Sum over the decode steps in the traced window of the least time the
chip could take for the attention the step needs, divided by the summed
device time of the decode-attention kernel, in %. What counts: each
layer that holds attention once (the model family's
``attention_layers(dims)`` in ``bench/families/<family>.py``), at
published head counts, over the live context (positions 0..pos, not
every cache slot). Work per layer at live context c:
  operations 4*H*hd*c; bytes 2*c*KV*hd*e (keys and values at the storage
  width e: 1 byte for a float8 device cache, 2 for bfloat16)
  + 4*H*hd (bf16 query in, output out).
The device segment's layers (below the cut) read a float8 cache where
the hop carries <= 8 bits, the server tail's a bfloat16 one.
Moves ``itl_p95_ms``.
"""
from __future__ import annotations

from bench.core.trace import device_trace

from bench.core.peaks import least_time_s


def is_kernel(name: str) -> bool:
    """A Pallas call of the decode-attention kernel. The trace names a
    device op by its HLO text; this kernel is the TPU custom call whose
    first operand is the scalar-prefetched position (s32[1]) followed by
    the query and the two cache blocks."""
    if 'custom_call_target="tpu_custom_call"' not in name:
        return False
    operands = name.split("custom-call(", 1)[-1].split(
        "), custom_call_target", 1)[0].split(", ")
    return len(operands) == 4 and operands[0].startswith("s32[1]")


def step_time_s(view, plan, context: int) -> float:
    n, kind = view.dims, view.device_kind
    H, KV, hd = n["H"], n["KV"], n["hd"]
    t = 0.0
    for layer in view.family.attention_layers(n):
        e = 1 if layer < plan.p and 0 < plan.bits_x <= 8 else 2
        t += least_time_s(4.0 * H * hd * context,
                          2.0 * context * KV * hd * e + 4.0 * H * hd, kind)
    return t


def read(view):
    tv = device_trace(view)
    spans = tv.of("decode") if tv is not None else []
    if not spans:
        return None
    kernel_ns = tv.op_ns(is_kernel, [(s.start, s.end) for s in spans])
    if kernel_ns == 0:
        return None
    recs = {r.index: r for r in view.records}
    least = 0.0
    for s in spans:
        r = recs[s.args["request"]]
        least += step_time_s(view, r.plan, r.prompt_len + s.args["step"])
    return 100.0 * least / (kernel_ns / 1e9)
