"""The reduction of the program's own spans and programs
(``bench/core/program_trace.py``) and the readers on it: the benchmark's
existing readers and summary read as before on the recorded chip trace,
the new readers read nothing there (it has no ``qpart.*`` span), give
hand-computed values on a hand-built view, and a program run is put
under the span that dispatched it, on a served-path trace recorded on
one v5e (``make_program_trace_fixture.py``)."""
from __future__ import annotations

import collections
import gzip
import os
import pathlib
import shutil
import tempfile
import types

import numpy as np
import pytest

from bench.core import program_trace, trace
from bench.core.spec import metric_reader
from bench.core.trace import Span, TraceView

FIXTURE = pathlib.Path(__file__).parent / "data" / "chip_trace.xplane.pb"
SERVED = pathlib.Path(__file__).parent / "data" / \
    "chip_program_trace.xplane.pb.gz"
NEW = ("deploy_ms", "session_prefill_us_per_token", "decode_host_ms",
       "server_ms_per_step")


@pytest.fixture(scope="module")
def tv():
    return trace.reduce(str(FIXTURE))


def _view(tv):
    return types.SimpleNamespace(trace=tv, records=[
        types.SimpleNamespace(index=0, prompt_len=8, serve_start=0.0,
                              serve_end=0.001)])


@pytest.mark.parametrize("name,value", [
    ("prefill_us_per_token", 0.103875),
    ("decode_step_ms", 0.006141),
    ("serve_idle_share", 99.65154211150652),
    ("plan_ms", 1.0),
])
def test_existing_readers_unchanged_on_the_fixture(tv, name, value):
    assert metric_reader(name).read(_view(tv)) == value


def test_summary_unchanged_on_the_fixture(tv):
    s = trace.device_summary(tv)
    assert (s["busy_s"], s["window_s"]) == (7.755e-06, 0.022421368)
    assert [d for _, d in s["breakdown"]["device_ops"]] == [
        5.082e-06, 1.052e-06, 5.79e-07, 5.05e-07, 4.19e-07, 9.2e-08,
        1.3e-08, 6e-09, 4e-09, 3e-09]
    assert s["breakdown"]["device_ops"][0][0].startswith(
        "%_lambda_.1 = bf16[4,4,64]")
    assert s["breakdown"]["idle_gaps"] == [
        ["arrival_wait", 0.020581246], ["serve", 0.000885171],
        ["prefill", 0.000743738], ["serve", 0.000203446],
        ["decode", 3e-09], ["serve", 2e-09], ["prefill", 2e-09],
        ["prefill", 2e-09], ["serve", 1e-09], ["decode", 1e-09]]


@pytest.fixture
def run_trace_dir(monkeypatch):
    """The fixture where ``bench/run.py`` keeps a run's trace: a
    ``bench-trace-*`` directory under a fresh temporary directory."""
    root = tempfile.mkdtemp(prefix="program-trace-test-")
    monkeypatch.setattr(tempfile, "tempdir", root)
    d = os.path.join(root, "bench-trace-x", "plugins", "profile", "r")
    os.makedirs(d)
    shutil.copy(FIXTURE, os.path.join(d, "host.xplane.pb"))
    program_trace._cache.clear()
    yield root
    program_trace._cache.clear()
    shutil.rmtree(root, ignore_errors=True)


def test_fixture_is_found_and_holds_no_program_span(tv, run_trace_dir):
    path = next(pathlib.Path(run_trace_dir).rglob("*.xplane.pb"))
    pv = program_trace.reduce(str(path), tv.offset_ns)
    assert pv.window == tv.window and pv.program == {}
    assert [m.run_id for m in pv.modules] == [16, 17, 18]
    assert {m.name for m in pv.modules} == {"jit__lambda"}
    assert program_trace.view_of(_view(tv)) is None


@pytest.mark.parametrize("name", NEW)
def test_new_readers_read_nothing_on_the_fixture(tv, run_trace_dir, name):
    assert metric_reader(name).read(_view(tv)) is None


def test_view_of_skips_a_trace_of_another_window(tv, run_trace_dir):
    moved = TraceView(tv.busy, tv.op_names, tv.op_start, tv.op_dur,
                      tv.spans, (tv.window[0] + 1, tv.window[1]),
                      tv.n_devices, tv.offset_ns, tv.n_paired)
    assert program_trace.view_of(_view(moved)) is None


def test_run_id_attribution_on_the_fixture(tv, run_trace_dir):
    """Each of the fixture's three program runs is enqueued inside the
    benchmark span the fixture opened around it."""
    path = next(pathlib.Path(run_trace_dir).rglob("*.xplane.pb"))
    pv = program_trace.reduce(str(path), tv.offset_ns)
    spans = [s for name in ("window", "serve", "generate", "prefill",
                            "decode") for s in tv.of(name)]
    nesting = program_trace.span_nesting(spans)
    chains = [nesting.chain(m.dispatched) for m in pv.modules]
    assert [c[-1].name for c in chains] == ["serve", "prefill", "decode"]
    assert [c[0].name for c in chains] == ["window"] * 3


def _span(name, s, e, **args):
    return Span(name, s, e, args)


def _hand_built():
    """Two requests' worth of program spans on a 1000 ns window: one
    deploy, one prefill and two decode steps, with device programs
    enqueued inside the stages. Device busy: [100, 160), [420, 470),
    [520, 560), [720, 800)."""
    busy = np.array([[100, 160], [420, 470], [520, 560], [720, 800]],
                    np.int64)
    tv = TraceView(busy, np.array([], object), np.zeros(0, np.int64),
                   np.zeros(0, np.int64), {}, (0, 1000), 1)
    program = {
        "plan": [_span("plan", 10, 20)],
        "split": [_span("split", 20, 50, p=30)],
        "stack": [_span("stack", 60, 70, hit=1)],
        "prefill": [_span("prefill", 80, 300, tokens=8, p=30)],
        "step": [_span("step", 400, 600, pos=8),
                 _span("step", 700, 900, pos=9)],
        "device": [_span("device", 90, 110), _span("device", 410, 430),
                   _span("device", 710, 730)],
        "server": [_span("server", 150, 170), _span("server", 450, 480),
                   _span("server", 750, 760)],
        "unembed": [_span("unembed", 480, 500), _span("unembed", 760,
                                                      770)],
    }
    M = program_trace.ModuleRun
    modules = [
        M("jit_embed", 100, 20, 1, 95, ("prefill", "device")),
        M("jit_extend_seg", 120, 40, 2, 155, ("prefill", "server")),
        M("jit_decode_seg", 420, 30, 3, 415, ("step", "device")),
        M("jit_decode_seg", 450, 20, 4, 460, ("step", "server")),
        M("jit_h_logits", 520, 40, 5, 490, ("step", "unembed")),
        M("jit_decode_seg", 720, 50, 6, 755, ("step", "server")),
        M("jit_argmax", 770, 30, 7, 765, ("step", "unembed")),
    ]
    pv = program_trace.ProgramView(program, modules, (0, 1000))
    return types.SimpleNamespace(trace=tv, records=[]), pv


@pytest.mark.parametrize("name,value", [
    # (30 + 10) ns of split and stack over one prefilled request
    ("deploy_ms", 40 / 1e6),
    # 20 + 40 ns of programs enqueued in the prefill over 8 tokens
    ("session_prefill_us_per_token", 60 / 1e3 / 8),
    # steps [400, 600) and [700, 900): busy 50 + 40 and 80 ns
    ("decode_host_ms", ((200 - 90) + (200 - 80)) / 2 / 1e6),
    # server and unembed programs of the steps: 20 + 40 + 50 + 30 ns
    ("server_ms_per_step", (20 + 40 + 50 + 30) / 2 / 1e6),
])
def test_new_readers_on_a_hand_built_view(monkeypatch, name, value):
    view, pv = _hand_built()
    monkeypatch.setattr(program_trace, "view_of", lambda v: pv)
    assert metric_reader(name).read(view) == pytest.approx(value, rel=1e-12)


def test_busy_in_matches_the_interval_overlap():
    view, _ = _hand_built()
    tv = view.trace
    starts = np.array([0, 110, 430, 465, 600, 900])
    ends = np.array([1000, 130, 530, 725, 900, 901])
    got = program_trace.busy_in(tv, starts, ends)
    want = [tv.busy_ns([(s, e)]) for s, e in zip(starts, ends)]
    np.testing.assert_array_equal(got, want)


def test_breakdown_by_span(monkeypatch):
    view, pv = _hand_built()
    b = program_trace.breakdown(view, pv)
    assert b["device_by_span"][:3] == [
        ["step/server | jit_decode_seg", 70e-9],
        ["prefill/server | jit_extend_seg", 40e-9],
        ["step/unembed | jit_h_logits", 40e-9]]
    idle = dict(b["idle_by_span"])
    # step [400, 600): own time [400, 410) + [430, 450) + [500, 600),
    # busy there 0 + 20 + 40: idle 10 + 0 + 60; step [700, 900): own
    # [700, 710) + [730, 750) + [770, 900), busy 0 + 20 + 30: idle 10 +
    # 0 + 100
    assert idle["step"] == pytest.approx((70 + 110) / 1e9)
    # prefill [80, 300): own [80, 90) + [110, 150) + [170, 300), busy
    # [110, 150) (40): idle 10 + 0 + 130
    assert idle["prefill"] == pytest.approx(140 / 1e9)
    assert idle["split"] == pytest.approx(30 / 1e9)
    assert idle["prefill/device"] == pytest.approx(10 / 1e9)
    assert len(b["idle_by_span"]) <= 10


def test_nesting_gives_the_chain_outermost_first():
    a = _span("a", 0, 100)
    b = _span("b", 10, 50)
    c = _span("c", 20, 30)
    d = _span("d", 60, 70)
    nesting = program_trace.span_nesting([d, c, b, a])
    assert [[s.name for s in nesting.chain(t)]
            for t in (25, 5, 40, 65, 100, -1)] == [
        ["a", "b", "c"], ["a"], ["a", "b"], ["a", "d"], [], []]
    assert [nesting.items[i].name if i >= 0 else None
            for i in nesting.parent] == [None, "a", "b", "a"]


@pytest.fixture
def served(monkeypatch):
    """The served-path trace where ``bench/run.py`` keeps a run's trace,
    and its ``TraceView``."""
    root = tempfile.mkdtemp(prefix="program-trace-test-")
    monkeypatch.setattr(tempfile, "tempdir", root)
    d = os.path.join(root, "bench-trace-y", "plugins", "profile", "r")
    os.makedirs(d)
    path = os.path.join(d, "host.xplane.pb")
    with gzip.open(SERVED) as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    program_trace._cache.clear()
    yield path, trace.reduce(path)
    program_trace._cache.clear()
    shutil.rmtree(root, ignore_errors=True)


def test_served_runs_fall_under_the_span_that_dispatched_them(served):
    """One session at p = L: prefill and two decode steps. Every program
    run lies under the stage whose code called it."""
    path, tv = served
    pv = program_trace.reduce(path, tv.offset_ns)
    assert [len(pv.of(n)) for n in ("prefill", "step", "device",
                                    "server", "unembed")] == [1, 2, 3, 3, 3]
    assert all(m.path for m in pv.modules)
    where = collections.Counter(
        (m.name, "/".join(m.path)) for m in pv.modules
        if m.name in ("jit_embed", "jit_extend_seg", "jit_decode_seg",
                      "jit_h_logits"))
    assert where == {
        ("jit_embed", "prefill/device"): 1,
        ("jit_extend_seg", "prefill/device"): 1,
        ("jit_extend_seg", "prefill/server"): 1,
        ("jit_h_logits", "prefill/unembed"): 1,
        ("jit_embed", "step/device"): 2,
        ("jit_decode_seg", "step/device"): 2,
        ("jit_decode_seg", "step/server"): 2,
        ("jit_h_logits", "step/unembed"): 2}


def test_served_enqueues_land_in_the_next_stage(served):
    """Why the flows are followed: the runtime enqueues a segment whose
    input is still being computed after the Python call has returned,
    while the next stage's code runs."""
    from jax.profiler import ProfileData
    path, tv = served
    pv = program_trace.reduce(path, tv.offset_ns)
    enqueued = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "DoEnqueueProgram":
                    enqueued[dict(ev.stats)["run_id"]] = int(ev.start_ns)
    spans = [s for v in pv.program.values() for s in v]
    seg = [m for m in pv.modules if m.name == "jit_decode_seg"]
    nesting = program_trace.span_nesting(spans)
    at_enqueue = [nesting.chain(enqueued[m.run_id]) for m in seg]
    assert {"/".join(s.name for s in c) for c in at_enqueue} == {
        "step/hop", "step/unembed"}
    assert {"/".join(m.path) for m in seg} == {"step/device", "step/server"}


def test_new_readers_on_the_served_trace(served):
    path, tv = served
    pv = program_trace.reduce(path, tv.offset_ns)
    view = types.SimpleNamespace(trace=tv, records=[])
    steps = pv.of("step")
    server = sum(m.dur for m in pv.modules if m.path[0] == "step"
                 and m.path[-1] in ("server", "unembed"))
    assert metric_reader("server_ms_per_step").read(view) == \
        server / 1e6 / len(steps)
    prefill = sum(m.dur for m in pv.modules if m.path[0] == "prefill")
    assert metric_reader("session_prefill_us_per_token").read(view) == \
        prefill / 1e3 / 128
    host = metric_reader("decode_host_ms").read(view)
    wall = np.mean([s.end - s.start for s in steps]) / 1e6
    assert 0 < host < wall
    # no split in a bare session: the stack lookup alone, a cache hit
    assert [s.args for s in pv.of("stack")] == [{"hit": 1}]
    assert metric_reader("deploy_ms").read(view) == pytest.approx(
        sum(s.end - s.start for s in pv.of("stack")) / 1e6)
