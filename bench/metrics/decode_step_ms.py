"""decode_step_ms — model step, decode (``DecodeSession.step``).

Device-busy milliseconds inside the intervals between consecutive
``stream_cb`` calls, divided by the number of such intervals. Each step
ends in ``block_until_ready``, so the intervals fence it. Moves
``itl_p95_ms``.
"""
from __future__ import annotations

from bench.core.trace import device_trace


def read(view):
    tv = device_trace(view)
    spans = tv.of("decode") if tv is not None else []
    if not spans:
        return None
    return tv.busy_ns([(s.start, s.end) for s in spans]) / 1e6 / len(spans)
