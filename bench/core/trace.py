"""Host spans written into the profiler's trace, and the reduction from a
recorded trace (``.xplane.pb``) to what the per-layer readers use.

The benchmark's own spans (``bench.<name>``, with the request index as
an argument) mark the window, each request's arrival wait, ``serve``
(planning) and ``generate``, and inside ``generate`` the prefill (entry
to the first token) and each decode step (one token to the next). They
sit on the trace's clock beside the device's operations, so device time
is attributed to a span by overlap: every jitted program of the program
is named ``jit_counted``, so names cannot tell its programs apart.

Device busy time is the union of the operations on the device planes'
``XLA Ops`` line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os

import numpy as np

PREFIX = "bench."


class Tracer:
    """Spans for the traced run; free when tracing is off."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self._open = None

    def span(self, name: str, **args):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(PREFIX + name, **args)

    def switch(self, name=None, **args) -> None:
        """Close the open manual span and, given a name, open the next."""
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
        if self.enabled and name is not None:
            self._open = self.span(name, **args)
            self._open.__enter__()


@dataclasses.dataclass
class Span:
    name: str
    start: int          # ns on the trace's clock
    end: int
    args: dict


@dataclasses.dataclass
class TraceView:
    busy: np.ndarray        # (k, 2) merged device-busy intervals, ns
    op_names: np.ndarray    # device operations in the window
    op_start: np.ndarray
    op_dur: np.ndarray
    spans: dict             # short span name -> [Span] in start order
    window: tuple           # (start, end) ns of the window span
    n_devices: int
    offset_ns: int = 0      # added to device times (clock_offset)
    n_paired: int = 0       # program runs the offset was read from

    def of(self, name: str) -> list:
        return self.spans.get(name, [])

    def busy_ns(self, intervals) -> float:
        """Device-busy ns inside the union of ``intervals`` [(s, e)]."""
        return overlap_ns(self.busy, merge(np.asarray(intervals, np.int64)
                                           .reshape(-1, 2)))

    def op_ns(self, match, intervals=None) -> float:
        """Summed duration of the device ops whose name ``match``
        accepts, inside ``intervals`` when given (op start inside)."""
        sel = np.array([bool(match(n)) for n in self.op_names], bool)
        if intervals is not None and sel.any():
            iv = merge(np.asarray(intervals, np.int64).reshape(-1, 2))
            idx = np.searchsorted(iv[:, 0], self.op_start, "right") - 1
            ok = idx >= 0
            ok[ok] &= self.op_start[ok] < iv[idx[ok], 1]
            sel &= ok
        return float(self.op_dur[sel].sum())


def merge(iv: np.ndarray) -> np.ndarray:
    """Union of half-open intervals, sorted and disjoint."""
    if len(iv) == 0:
        return np.zeros((0, 2), np.int64)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.int64)


def overlap_ns(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the intersection of two merged interval sets."""
    i = j = 0
    total = 0
    while i < len(a) and j < len(b):
        lo = max(a[i, 0], b[j, 0])
        hi = min(a[i, 1], b[j, 1])
        if hi > lo:
            total += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return float(total)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {paths}")
    return paths[0]


def clock_offset(enqueued: dict, started: dict) -> int:
    """ns to add to device times to put them on the host's clock.

    The trace's device clock is not the host's (on a v5e host it ran
    0.4-2 ms behind). The host's ``DoEnqueueProgram`` event and the
    device's ``XLA Modules`` event of one program execution share a
    ``run_id``, and the device cannot start a program before the host
    enqueues it: offset >= enqueue - device start for every run, with
    near equality for a program enqueued onto an idle device. So the
    offset is the largest such difference. (Pairing launches and device
    programs by order fails where some launches run no device program.)"""
    diffs = [enqueued[r] - started[r] for r in started.keys() & enqueued]
    return int(max(diffs)) if diffs else 0


def reduce(path: str) -> TraceView:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans: dict = {}
    ops = []
    devices = 0
    enqueued, started = {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                "SparseCore" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            devices += 1
            for ev in lines["XLA Ops"].events:
                ops.append((ev.name, int(ev.start_ns), int(ev.duration_ns)))
            if "XLA Modules" in lines:
                for ev in lines["XLA Modules"].events:
                    run = dict(ev.stats).get("run_id")
                    if run is not None:
                        started[run] = int(ev.start_ns)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(PREFIX):
                        s = int(ev.start_ns)
                        spans.setdefault(ev.name[len(PREFIX):], []).append(
                            Span(ev.name[len(PREFIX):], s,
                                 s + int(ev.duration_ns), dict(ev.stats)))
                    elif ev.name == "DoEnqueueProgram":
                        run = dict(ev.stats).get("run_id")
                        if run is not None:
                            enqueued[run] = int(ev.start_ns)
    offset = clock_offset(enqueued, started) if devices == 1 else 0
    for v in spans.values():
        v.sort(key=lambda sp: sp.start)
    if "window" not in spans:
        raise RuntimeError("trace holds no bench.window span")
    w = spans["window"][0]
    names = np.array([o[0] for o in ops], object)
    start = np.array([o[1] for o in ops], np.int64) + offset
    dur = np.array([o[2] for o in ops], np.int64)
    keep = (start >= w.start) & (start < w.end)
    names, start, dur = names[keep], start[keep], dur[keep]
    busy = merge(np.stack([start, np.minimum(start + dur, w.end)], 1)) \
        if len(start) else np.zeros((0, 2), np.int64)
    return TraceView(busy, names, start, dur, spans, (w.start, w.end),
                     devices, offset, len(enqueued.keys() & started))


def device_trace(view):
    """The run's trace where it holds a device plane, else None: a run
    off the chip has no device time to read."""
    tv = view.trace
    return tv if tv is not None and tv.n_devices else None


def short_name(name: str, width: int = 160) -> str:
    """An op's HLO text cut to its name and the start of its signature."""
    return name if len(name) <= width else name[:width] + "..."


def device_summary(tv: TraceView, top: int = 10) -> dict:
    """busy_s / window_s over the whole window (per chip), and the
    breakdown: the device ops that took most time, and the longest idle
    gaps labelled by the innermost benchmark span around each."""
    w0, w1 = tv.window
    busy_s = float((tv.busy[:, 1] - tv.busy[:, 0]).sum()) / 1e9 \
        / max(tv.n_devices, 1)
    by_name: dict = {}
    for n, d in zip(tv.op_names, tv.op_dur):
        by_name[n] = by_name.get(n, 0) + int(d)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    ops = [(short_name(n), d) for n, d in ops]
    edges = np.concatenate([[w0], tv.busy.ravel(), [w1]]).reshape(-1, 2)
    gaps = [(int(s), int(e)) for s, e in edges if e > s]
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = []
    for s, e in gaps[:top]:
        mid = (s + e) // 2
        label, width = "window", w1 - w0
        for name, lst in tv.spans.items():
            for sp in lst:
                if sp.start <= mid < sp.end and sp.end - sp.start < width:
                    label, width = name, sp.end - sp.start
        labelled.append([label, (e - s) / 1e9])
    return {"busy_s": busy_s, "window_s": (w1 - w0) / 1e9,
            "breakdown": {"device_ops": [[n, d / 1e9] for n, d in ops],
                          "idle_gaps": labelled}}

