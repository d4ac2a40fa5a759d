"""session_prefill_us_per_token — model step, prefill
(``serving/decode/pipeline.py`` ``DecodeSession.prefill``).

Device microseconds of the program runs enqueued inside the program's
``qpart.prefill`` spans (embed, device segment, hop, server segment,
unembed), summed and divided by the prompt tokens those spans carry
(their ``tokens`` argument). Unlike ``prefill_us_per_token`` it holds
none of the per-request ``split``, which runs before the span. Moves
``ttft_p50_ms``. None where the trace holds no such span.
"""
from __future__ import annotations

from bench.core import program_trace
from bench.core.trace import device_trace


def read(view):
    pv = program_trace.view_of(view)
    if pv is None or device_trace(view) is None:
        return None
    spans = pv.of("prefill")
    tokens = sum(int(s.args["tokens"]) for s in spans)
    if not tokens:
        return None
    ns = sum(m.dur for m in pv.modules if m.path and m.path[0] == "prefill")
    return ns / 1e3 / tokens
