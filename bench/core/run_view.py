"""What one run recorded, and the end-to-end metrics taken from it.

Times are host-clock seconds (``time.perf_counter``). A request is timed
from its due time, so queueing behind earlier requests counts.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from bench.core import stats


@dataclasses.dataclass
class Record:
    index: int
    prompt_len: int
    n_new: int
    due: float
    late_s: Optional[float] = None      # generator lateness when idle
    serve_start: Optional[float] = None
    serve_end: Optional[float] = None
    gen_start: Optional[float] = None
    plan: object = None
    token_times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False

    @property
    def ttft_s(self) -> float:
        return self.token_times[0] - self.due

    @property
    def gaps_s(self) -> np.ndarray:
        return np.diff(np.asarray(self.token_times))


@dataclasses.dataclass
class RunView:
    """Everything a per-layer reader may read."""
    records: list
    t0: float                 # window start (host clock)
    seconds: float
    dims: dict                # published sizes (family.dims)
    device_kind: str
    family: object            # bench/families/<family>.py: work counts
    trace: object = None      # trace.TraceView of the traced run, or None

    @property
    def served(self) -> list:
        return [r for r in self.records if r.done]


def end_to_end(view: RunView, drain_end: float) -> dict:
    """TTFT over every request due in the window (a failed request counts
    at the drain's end: it has at least that latency), the gaps between
    consecutive tokens of every request, and output tokens completed
    inside the window per window second."""
    ttft = [r.ttft_s if r.done else drain_end - r.due for r in view.records]
    gaps = np.concatenate([r.gaps_s for r in view.records if r.done]
                          or [np.zeros(0)])
    close = view.t0 + view.seconds
    in_window = sum(int(np.sum(np.asarray(r.token_times) <= close))
                    for r in view.records)
    out = {"ttft_p50_ms": stats.percentile(ttft, 50) * 1e3,
           "ttft_p90_ms": stats.percentile(ttft, 90) * 1e3,
           "tokens_per_s": stats.rate(in_window, view.seconds)}
    if len(gaps):
        out["itl_p95_ms"] = stats.percentile(gaps, 95) * 1e3
    return out


def window_summary(records, t0: float, seconds: float) -> dict:
    """For the knee sweep: how busy the server was, and how long the
    requests waited before service (the median request, and the first
    and last thirds in arrival order)."""
    done = [r for r in records if r.done]
    service = [r.token_times[-1] - r.serve_start for r in done]
    wait = [r.serve_start - r.due for r in done]
    third = max(len(wait) // 3, 1)

    def mean(x):
        return float(np.mean(x)) if x else None

    return {"requests": len(records), "served": len(done),
            "service_s_mean": mean(service),
            "busy_share": float(np.sum(service)) / seconds,
            "wait_s_median": float(np.median(wait)) if wait else None,
            "wait_s_first_third": mean(wait[:third]),
            "wait_s_last_third": mean(wait[-third:])}
