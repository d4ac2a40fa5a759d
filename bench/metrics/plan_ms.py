"""plan_ms — planner layer (``serving/qpart_server.py`` ``serve``).

Host-clock milliseconds around ``QPARTServer.serve``, the mean over the
requests the window served. Moves ``ttft_p50_ms``: planning sits on the
path to every first token.
"""
from __future__ import annotations

import numpy as np


def read(view):
    t = [r.serve_end - r.serve_start for r in view.records
         if r.serve_end is not None]
    return 1e3 * float(np.mean(t)) if t else None
