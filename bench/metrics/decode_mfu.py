"""decode_mfu — the whole decode step's share of the chip's bf16 peak.

Useful model operations of every decode step in the traced window,
divided by the summed wall time of the decode intervals times the peak.
Useful means the published model's: published head counts (no head
padding), each layer once (not the masked full-depth passes), the live
context (not every cache slot), and one unembedding. The operations per
step at a live context are the model family's count
(``decode_flops(dims, context)`` in ``bench/families/<family>.py``).
Moves ``itl_p95_ms``.
"""
from __future__ import annotations

from bench.core.trace import device_trace

from bench.core.peaks import peaks


def read(view):
    tv = device_trace(view)
    spans = tv.of("decode") if tv is not None else []
    if not spans:
        return None
    prompt = {r.index: r.prompt_len for r in view.records}
    flops = 0.0
    wall = 0
    for s in spans:
        # step j feeds the token at position prompt + j - 1 and attends
        # over positions 0..prompt + j - 1
        flops += view.family.decode_flops(
            view.dims, prompt[s.args["request"]] + s.args["step"])
        wall += s.end - s.start
    peak = peaks(view.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / (wall / 1e9 * peak)
