"""decode_host_ms — model step, host (``serving/decode/pipeline.py``
``DecodeSession.step`` and the token's copy to the host in
``round_stream``).

For each of the program's ``qpart.step`` spans (one decode token, from
``step()`` to the token as a host array), its wall time less the device-
busy time inside it; the mean, in milliseconds: the part of a token gap
in which the device waits on the host (dispatch, the eager hop, the
stage fence, the argmax and the copies). Moves ``itl_p95_ms``. None
where the trace holds no such span.
"""
from __future__ import annotations

import numpy as np

from bench.core import program_trace
from bench.core.trace import device_trace


def read(view):
    pv = program_trace.view_of(view)
    tv = device_trace(view)
    if pv is None or tv is None or not pv.of("step"):
        return None
    starts = np.array([s.start for s in pv.of("step")], np.int64)
    ends = np.array([s.end for s in pv.of("step")], np.int64)
    idle = (ends - starts) - program_trace.busy_in(tv, starts, ends)
    return float(idle.mean()) / 1e6
