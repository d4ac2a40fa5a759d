"""prefill_us_per_token — model step, prefill (``serving/decode/pipeline.py``
``DecodeSession.prefill``, ``models/transformer.py`` ``segment_extend``).

Device-busy microseconds inside each request's prefill interval (from
``generate()`` entry to its first ``stream_cb``), summed and divided by
the prompt tokens of those requests. Until the program's own spans split
them, the interval also holds the per-request quantization of the device
segment (``backend.split``) that ``generate`` runs first. Moves
``ttft_p50_ms``.
"""
from __future__ import annotations

from bench.core.trace import device_trace


def read(view):
    tv = device_trace(view)
    spans = tv.of("prefill") if tv is not None else []
    if not spans:
        return None
    prompt = {r.index: r.prompt_len for r in view.records}
    tokens = sum(prompt[s.args["request"]] for s in spans)
    return tv.busy_ns([(s.start, s.end) for s in spans]) / 1e3 / tokens
