"""deploy_ms — deploy (``serving/deployment.py`` ``device_segment`` ->
``backend.split``, and the ``stacked_for``/``qstacked_for`` lookup in
``DecodeSession.__init__``).

Host-clock milliseconds of the program's ``qpart.split`` and
``qpart.stack`` spans, summed and divided by the requests prefilled in
the traced window (``qpart.prefill`` spans): what deploying the
quantized device segment costs each request before its prefill starts.
Moves ``ttft_p50_ms``. None where the trace holds no such span.
"""
from __future__ import annotations

from bench.core import program_trace


def read(view):
    pv = program_trace.view_of(view)
    if pv is None:
        return None
    spans = pv.of("split") + pv.of("stack")
    requests = len(pv.of("prefill"))
    if not spans or not requests:
        return None
    return sum(s.end - s.start for s in spans) / 1e6 / requests
