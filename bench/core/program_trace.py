"""The program's own spans and programs in a recorded trace, read beside
what ``trace.reduce`` reads (which this module leaves as it is).

The served path marks its phases as ``qpart.<name>`` host spans
(``repro/serving/tracing.py``: ``plan``, ``split``, ``stack``,
``prefill`` and ``step``, and inside the last two ``device``, ``hop``,
``fence``, ``server``, ``unembed`` and ``sync``) and names each jitted
program after its key (``jit_embed``, ``jit_decode_seg``, ...). From the
run's ``.xplane.pb`` this module collects:

- ``program``: the ``qpart.*`` spans, short name -> [Span] in start
  order;
- ``modules``: every program run on the device (the device plane's
  ``XLA Modules`` line) whose start lies in the window: its name without
  the ``(fingerprint)``, start and duration on the host's clock (the
  ``run_id`` offset ``trace.reduce`` found), and the chain of ``qpart.*``
  spans, outermost first, around the host call that dispatched it.

The dispatching call is found from the run's ``DoEnqueueProgram`` (same
``run_id``) by following the trace's flow events back to the Python
thread: each host event carries the id of the flow it continues (stat
``_c``) and of the one it starts (``_p``). On the chip the enqueue of a
program whose inputs are still being computed is deferred to a runtime
thread and lands in whatever span the Python thread has moved on to, and
the device runs it later still; the Python call (its ``PJRT_..._Execute
linkage`` event) lies in the span whose code made it. Where the flows
end early, the last event reached stands in.

A program without these spans (an older tree) gives an empty ``program``
and readers that return ``None``.

The harness hands a reader the reduced ``TraceView``, not the trace's
path: ``view_of`` finds the run's own trace where ``bench/run.py``
records it (a ``bench-trace-*`` directory under the temporary directory)
and takes the one whose ``bench.window`` span is the view's window.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import tempfile
from typing import Optional

import numpy as np

from bench.core.trace import PREFIX as BENCH_PREFIX
from bench.core.trace import Span, device_trace

PREFIX = "qpart."

_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class ModuleRun:
    name: str           # program name, e.g. "jit_decode_seg"
    start: int          # ns on the host's clock
    dur: int
    run_id: int
    dispatched: int     # host ns of the call that dispatched it (-1: none)
    path: tuple = ()    # names of the qpart.* spans around that call


@dataclasses.dataclass
class ProgramView:
    program: dict       # short span name -> [Span] in start order
    modules: list       # [ModuleRun] in start order
    window: tuple       # (start, end) ns of the bench.window span

    def of(self, name: str) -> list:
        return self.program.get(name, [])


class Nesting:
    """Intervals of one thread, which therefore nest, for the query:
    which of them are open at time t (start <= t < end)."""

    def __init__(self, items, bounds):
        order = sorted(range(len(items)),
                       key=lambda i: (bounds[i][0], -bounds[i][1]))
        self.items = [items[i] for i in order]
        self.bounds = [bounds[i] for i in order]
        self.starts = [b[0] for b in self.bounds]
        self.parent = []
        stack: list = []
        for i, (start, _) in enumerate(self.bounds):
            while stack and self.bounds[stack[-1]][1] <= start:
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            stack.append(i)

    def open_at(self, t: int) -> int:
        """Index of the innermost interval open at ``t``, or -1."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.bounds[i][1] <= t:
            i = self.parent[i]
        return i

    def chain(self, t: int) -> tuple:
        """The items open at ``t``, outermost first."""
        out = []
        i = self.open_at(t)
        while i >= 0:
            out.append(self.items[i])
            i = self.parent[i]
        return tuple(reversed(out))


def span_nesting(spans) -> Nesting:
    return Nesting(spans, [(s.start, s.end) for s in spans])


def _dispatch_time(line, t, flows, producers) -> int:
    """Follow flows back from the host event at ``t`` on ``line``: the
    innermost open event there continues a flow; jump to the event that
    started it, and repeat until no flow continues."""
    seen = set()
    while (line, t) not in seen and line in flows:
        seen.add((line, t))
        i = flows[line].open_at(t)
        if i < 0 or flows[line].items[i] not in producers:
            break
        line, t = producers[flows[line].items[i]]
    return t


def reduce(path: str, offset_ns: int) -> ProgramView:
    """The ``qpart.*`` spans and the device's program runs of one trace;
    ``offset_ns`` puts device times on the host's clock (the trace's
    ``TraceView.offset_ns``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    program: dict = {}
    window = None
    enqueued: dict = {}          # run_id -> (line, host ns)
    flows: dict = {}             # line -> ([flow continued], [(start, end)])
    producers: dict = {}         # flow id -> (line, host ns it started)
    runs = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                "SparseCore" not in plane.name:
            for ln in plane.lines:
                if ln.name != "XLA Modules":
                    continue
                for ev in ln.events:
                    run = dict(ev.stats).get("run_id")
                    runs.append((_FINGERPRINT.sub("", ev.name),
                                 int(ev.start_ns) + offset_ns,
                                 int(ev.duration_ns), run))
        elif plane.name.startswith("/host:"):
            for li, ln in enumerate(plane.lines):
                line = (plane.name, li)
                for ev in ln.events:
                    name = ev.name
                    s = int(ev.start_ns)
                    if name.startswith(PREFIX):
                        short = name[len(PREFIX):]
                        program.setdefault(short, []).append(
                            Span(short, s, s + int(ev.duration_ns),
                                 dict(ev.stats)))
                        continue
                    if name == BENCH_PREFIX + "window":
                        window = (s, s + int(ev.duration_ns))
                    if name.startswith("$"):     # Python-tracer frames
                        continue
                    st = dict(ev.stats)
                    if "_c" in st:
                        ids, bounds = flows.setdefault(line, ([], []))
                        ids.append(st["_c"])
                        bounds.append((s, s + int(ev.duration_ns)))
                    if "_p" in st:
                        producers[st["_p"]] = (line, s)
                    if name == "DoEnqueueProgram" and "run_id" in st:
                        enqueued[st["run_id"]] = (line, s)
    for v in program.values():
        v.sort(key=lambda sp: sp.start)
    if window is not None:
        runs = [r for r in runs if window[0] <= r[1] < window[1]]
    runs.sort(key=lambda r: r[1])
    flows = {line: Nesting(*f) for line, f in flows.items()}
    spans = span_nesting([s for v in program.values() for s in v])
    modules = []
    for n, s, d, run in runs:
        t = _dispatch_time(*enqueued[run], flows, producers) \
            if run in enqueued else -1
        modules.append(ModuleRun(n, s, d, -1 if run is None else int(run),
                                 t, tuple(sp.name
                                          for sp in spans.chain(t))))
    return ProgramView(program, modules, window)


# the last trace reduced, for the readers of one run (each gets the view)
_cache: dict = {}


def view_of(view) -> Optional[ProgramView]:
    """The ``ProgramView`` of the run behind ``view`` (a ``RunView`` with
    a ``TraceView``), or None where its trace cannot be found or holds
    no ``qpart.*`` span. Reduced once per trace."""
    tv = getattr(view, "trace", None)
    if tv is None:
        return None
    key = (tv.window, tv.offset_ns)
    if key not in _cache:
        _cache.clear()
        _cache[key] = None
        pattern = os.path.join(tempfile.gettempdir(), "bench-trace-*", "**",
                               "*.xplane.pb")
        for path in sorted(glob.glob(pattern, recursive=True),
                           key=os.path.getmtime, reverse=True):
            pv = reduce(path, tv.offset_ns)
            if pv.window == tuple(tv.window):
                _cache[key] = pv if pv.program else None
                break
    return _cache[key]


def busy_in(tv, starts, ends) -> np.ndarray:
    """Device-busy ns of ``tv`` inside each [start, end) (vectorized over
    the merged busy intervals)."""
    a, b = tv.busy[:, 0], tv.busy[:, 1]
    cum = np.concatenate([[0], np.cumsum(b - a)])

    def before(t):
        t = np.asarray(t, np.int64)
        i = np.searchsorted(a, t, "right") - 1
        ok = i >= 0
        out = np.zeros(t.shape, np.int64)
        out[ok] = cum[i[ok]] + np.minimum(t[ok], b[i[ok]]) - a[i[ok]]
        return out

    return (before(ends) - before(starts)).astype(np.float64)


def _label(path: tuple) -> str:
    return "/".join(path) if path else "-"


def breakdown(view, pv: ProgramView, top: int = 10) -> dict:
    """Device seconds by dispatching span chain and program
    (``device_by_span``), and device-idle seconds of each span's own
    time, its children's taken out, by span chain (``idle_by_span``):
    each the ``top`` largest, as [label, seconds]."""
    by_module: dict = {}
    for m in pv.modules:
        k = f"{_label(m.path)} | {m.name}"
        by_module[k] = by_module.get(k, 0) + m.dur
    out = {"device_by_span": [[k, d / 1e9] for k, d in sorted(
        by_module.items(), key=lambda kv: -kv[1])[:top]]}
    tv = device_trace(view)
    if tv is None:
        return out
    nesting = span_nesting([s for v in pv.program.values() for s in v])
    spans = nesting.items
    starts = np.array([s.start for s in spans], np.int64)
    ends = np.array([s.end for s in spans], np.int64)
    idle = (ends - starts) - busy_in(tv, starts, ends)
    own = idle.copy()
    labels = []
    for i, s in enumerate(spans):
        up = nesting.parent[i]
        labels.append(s.name if up < 0 else f"{labels[up]}/{s.name}")
        if up >= 0:
            own[up] -= idle[i]
    by_span: dict = {}
    for lab, v in zip(labels, own):
        by_span[lab] = by_span.get(lab, 0.0) + float(v)
    out["idle_by_span"] = [[k, v / 1e9] for k, v in sorted(
        by_span.items(), key=lambda kv: -kv[1])[:top]]
    return out
