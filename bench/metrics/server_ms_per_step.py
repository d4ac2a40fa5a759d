"""server_ms_per_step — server tail and head (``serving/decode/
pipeline.py`` ``DecodeSession.step``: the server's ``decode_seg`` over
layers [p, L), ``hidden_logits`` and the argmax).

Device milliseconds of the program runs enqueued inside the
``qpart.server`` and ``qpart.unembed`` spans of the program's
``qpart.step`` spans, divided by the number of steps. The device
segment and the server tail run the same ``jit_decode_seg`` program;
the span that enqueued a run tells them apart. Moves ``itl_p95_ms``.
None where the trace holds no such span.
"""
from __future__ import annotations

from bench.core import program_trace
from bench.core.trace import device_trace


def read(view):
    pv = program_trace.view_of(view)
    if pv is None or device_trace(view) is None or not pv.of("step"):
        return None
    ns = sum(m.dur for m in pv.modules
             if len(m.path) > 1 and m.path[0] == "step"
             and m.path[-1] in ("server", "unembed"))
    return ns / 1e6 / len(pv.of("step"))
