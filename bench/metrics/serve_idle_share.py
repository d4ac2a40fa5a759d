"""serve_idle_share — device (``jax`` runtime on the chip).

Share of the wall time in which a request is in service (its ``serve``
and ``generate`` intervals) during which no operation runs on the
device: 100 * (1 - busy / wall). Moves ``itl_p95_ms``: host work between
dispatches is what a decode step waits on when the device is idle.
"""
from __future__ import annotations

import numpy as np

from bench.core.trace import device_trace, merge


def read(view):
    tv = device_trace(view)
    spans = (tv.of("serve") + tv.of("generate")) if tv is not None else []
    if not spans:
        return None
    iv = merge(np.asarray([(s.start, s.end) for s in spans], np.int64))
    wall = float((iv[:, 1] - iv[:, 0]).sum())
    return 100.0 * (1.0 - tv.busy_ns(iv) / wall)
