"""Program names, trace and cache counters, and the ``qpart.*`` spans of
the served path (``serving/tracing.py``): every jitted program lowers to
a module named after its key, per-program trace counters sum to
``trace_count``, the stacked-tree caches count their hits, misses and
evictions, and a traced ``Deployment.generate`` writes one prefill span
and one span per decode step with the stages nested inside them."""
import contextlib
import dataclasses
import functools
import glob
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.cost_model import Channel, DeviceProfile, ObjectiveWeights
from repro.core.solver import PartitionPlan
from repro.models import transformer as T
from repro.serving import decode
from repro.serving.backends import TransformerBackend
from repro.serving.backends.transformer import _STACKED_CACHE_SLOTS
from repro.serving.decode import DecodeSession, pipeline
from repro.serving.qpart_server import QPARTServer
from repro.serving.simulator import InferenceRequest

KEY = jax.random.key(0)
SEQ = 16
MAX_LEN = 48


def _plan(p: int, bits: float = 8.0) -> PartitionPlan:
    return PartitionPlan(p=p, bits_w=np.full(p, float(bits)),
                         bits_x=float(bits), objective=0.0, psi_total=0.0,
                         payload_bits=0.0, breakdown={})


@pytest.fixture(scope="module")
def lm():
    cfg = dataclasses.replace(
        get_config("smollm-135m").reduced(), name="smollm-tracing",
        d_model=64, num_heads=2, num_kv_heads=1, head_dim=32, d_ff=128,
        vocab_size=32, tp_pad=1, dtype="float32")
    return cfg, T.init_params(KEY, cfg)


def _backend(lm):
    cfg, params = lm
    return TransformerBackend(cfg, params, seq_len=SEQ,
                              decode_max_len=MAX_LEN)


def _program_args(b: TransformerBackend):
    """(program, args) for every jitted program of the backend."""
    cfg, L = b.cfg, b.num_layers
    toks = jnp.zeros((1, SEQ), jnp.int32)
    h = jnp.zeros((1, SEQ, cfg.d_model), jnp.float32)
    x = h[:, :1]
    cache = T.init_cache(cfg, 1, MAX_LEN, jnp.float32)
    pos = jnp.int32(SEQ)
    return {
        "tokens_logits": (b._tokens_logits(), (b.params, toks, 0, L)),
        "h_logits": (b._h_logits(), (b.params, h, 0, L)),
        "acts": (b._acts(), (b.params, toks)),
        "cut": (b._cut(), (b.params, toks, 1)),
        "embed": (b._embed_prog(), (b.params, toks)),
        "prefill_seg": (b._prefill_seg(), (b.params, h, cache, 0, L)),
        "decode_seg": (b._decode_seg(), (b.params, x, cache, pos, 0, L)),
        "extend_seg": (b._extend_seg(), (b.params, h, cache, pos, 0, L)),
        "verify_seg": (b._verify_seg(), (b.params, h[:, :2], cache, pos,
                                         0, L)),
        "unembed": (b._unembed_prog(),
                    ({k: v for k, v in b.params.items() if k != "blocks"},
                     x)),
    }


PROGRAMS = ("tokens_logits", "h_logits", "acts", "cut", "embed",
            "prefill_seg", "decode_seg", "extend_seg", "verify_seg",
            "unembed")


class TestProgramNames:
    @pytest.mark.parametrize("name", PROGRAMS)
    def test_program_lowers_to_module_named_after_key(self, lm, name):
        fn, args = _program_args(_backend(lm))[name]
        text = fn.lower(*args).as_text()
        assert text.startswith(f"module @jit_{name} "), text[:80]

    def test_tuple_key_names_program_after_first_element(self, lm):
        b = _backend(lm)
        toks = jnp.zeros((2, SEQ), jnp.int32)
        b.calibrate_probes(toks)
        (fn,) = [f for k, f in b.__dict__["_jit_cache"].items()
                 if isinstance(k, tuple) and k[0] == "probe_all"]
        assert fn.lower(b.params, toks).as_text().startswith(
            "module @jit_probe_all ")
        assert b.counters["trace.probe_all"] == 1


class TestTraceCounters:
    def test_per_program_counters_sum_to_trace_count(self, lm):
        b = _backend(lm)
        assert b.trace_count == 0 and b.counters == {}
        prompt = np.zeros((1, 8), np.int32)
        for p in (0, 1, b.num_layers):
            DecodeSession(b, _plan(p), max_len=MAX_LEN).generate(prompt, 3)
        traces = {k: v for k, v in b.counters.items()
                  if k.startswith("trace.")}
        assert {"trace.embed", "trace.extend_seg", "trace.decode_seg",
                "trace.unembed"} <= set(traces)
        assert all(v >= 1 for v in traces.values())
        assert sum(traces.values()) == b.trace_count

    def test_repeated_generate_adds_no_trace(self, lm):
        b = _backend(lm)
        prompt = np.zeros((1, 8), np.int32)
        plan = _plan(1)
        DecodeSession(b, plan, max_len=MAX_LEN).generate(prompt, 4)
        before = {k: v for k, v in b.counters.items()
                  if k.startswith("trace.")}
        DecodeSession(b, plan, max_len=MAX_LEN).generate(prompt + 1, 4)
        after = {k: v for k, v in b.counters.items()
                 if k.startswith("trace.")}
        assert after == before


class TestStackCounters:
    def test_plans_beyond_the_slots_hit_miss_and_evict(self, lm):
        b = _backend(lm)
        n = _STACKED_CACHE_SLOTS + 2
        plans = [_plan(1, bits) for bits in range(3, 3 + n)]
        for plan in plans:
            b.stacked_for(b.split(plan), plan)
        assert (b.counters.get("stack.hit", 0), b.counters["stack.miss"],
                b.counters["stack.evict"]) == (0, n, 2)
        # the newest _STACKED_CACHE_SLOTS plans are held: all hits
        for plan in plans[-_STACKED_CACHE_SLOTS:]:
            b.stacked_for(b.split(plan), plan)
        assert b.counters["stack.hit"] == _STACKED_CACHE_SLOTS
        # the oldest was evicted: a miss that evicts once more
        b.stacked_for(b.split(plans[0]), plans[0])
        assert (b.counters["stack.miss"], b.counters["stack.evict"]) == \
            (n + 1, 3)

    def test_struct_cache_counts_in_the_same_dict(self, lm):
        b = _backend(lm)
        plan = _plan(1, 8.0)
        seg = b.split(plan)
        b.qstacked_for(seg, plan)
        b.qstacked_for(seg, plan)
        assert (b.counters["stack.miss"], b.counters["stack.hit"]) == (1, 1)
        assert "stack.evict" not in b.counters


def _served(lm):
    """A server whose store plans a device cut (cheap server time
    would offload everything; a slow server pushes layers down)."""
    cfg, params = lm
    from repro.core.cost_model import ServerProfile
    srv = QPARTServer(ServerProfile(f_clock=1e7))
    backend = _backend(lm)
    toks = np.asarray(jax.random.randint(KEY, (8, SEQ), 0, cfg.vocab_size))
    srv.register("lm", backend, toks, np.zeros(8, np.int32))
    m = srv.models["lm"]
    L = cfg.num_layers
    m.s_w, m.s_x, m.rho = (np.ones(L), np.ones(L), np.full(L, 0.1))
    m.delta_table = {a: a * 50 for a in srv.levels}
    dev = DeviceProfile(memory_bytes=2e9)
    ch = Channel(capacity_bps=200e6)
    w = ObjectiveWeights()
    srv.build_store("lm", dev, ch, w)
    return srv, InferenceRequest("lm", 0.05, dev, ch, w)


class TestSplitMaterialize:
    """The served path builds a segment's fake-quantized weights only
    when a tree is built from them: never on the kernel path, once per
    stacked-tree miss on the dense path."""

    def _generate_twice(self, lm, monkeypatch, qkernels):
        monkeypatch.setattr(decode, "DecodeSession", functools.partial(
            DecodeSession, qkernels=qkernels))
        srv, req = _served(lm)
        backend = srv.models["lm"].backend
        prompt = np.zeros((1, 8), np.int32)
        deps, tokens, counts = [], [], []
        for _ in range(2):
            dep = srv.serve(req)
            tokens.append(dep.generate(prompt, 4).tokens)
            deps.append(dep)
            counts.append(backend.counters["split.materialize"])
        assert deps[0].plan is deps[1].plan and deps[0].plan.p > 0
        return backend, deps[0], prompt, tokens, counts

    def test_kernel_path_never_materializes(self, lm, monkeypatch):
        backend, dep, prompt, tokens, counts = self._generate_twice(
            lm, monkeypatch, qkernels=True)
        plan = dep.plan
        assert max(dep.device_segment().segment.bits_w) <= 8
        assert counts == [0, 0]
        # the tokens of a segment split eagerly, its weights built
        # before the session, on the dense fake-quant path
        seg = backend.split(plan)
        assert len(seg.params) == plan.p
        want = DecodeSession(backend, plan, max_len=MAX_LEN, segment=seg,
                             qkernels=False).generate(prompt, 4).tokens
        for got in tokens:
            np.testing.assert_array_equal(got, want)

    def test_dense_path_materializes_once_per_tree(self, lm, monkeypatch):
        backend, _, _, _, counts = self._generate_twice(
            lm, monkeypatch, qkernels=False)
        assert counts == [1, 1]
        assert (backend.counters["stack.miss"],
                backend.counters["stack.hit"]) == (1, 1)


def _program_spans(path: str) -> list:
    """[(name, start, end, args)] of the qpart.* host events, by start."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("qpart."):
                    s = int(ev.start_ns)
                    out.append((ev.name[len("qpart."):], s,
                                s + int(ev.duration_ns), dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


class TestSpans:
    def test_generate_writes_prefill_steps_and_nested_stages(
            self, lm, tmp_path):
        srv, req = _served(lm)
        n = 5
        prompt = np.zeros((1, 8), np.int32)
        # warm: compile outside the trace
        srv.serve(req).generate(prompt, n)
        with jax.profiler.trace(str(tmp_path)):
            dep = srv.serve(req)
            dep.generate(prompt, n)
        assert dep.plan.p > 0
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        spans = _program_spans(path)
        by = {}
        for e in spans:
            by.setdefault(e[0], []).append(e)
        assert len(by["plan"]) == 1
        assert [e[3] for e in by["split"]] == [{"p": dep.plan.p}]
        assert [e[3] for e in by["stack"]] == [{"hit": 1}]
        (pre,) = by["prefill"]
        assert pre[3] == {"tokens": 8, "p": dep.plan.p}
        steps = by["step"]
        assert [s[3]["pos"] for s in steps] == list(range(8, 8 + n - 1))
        stages = ("device", "hop", "fence", "unembed", "sync")
        if dep.plan.p < lm[0].num_layers:
            stages += ("server",)
        for outer in [pre] + steps:
            inside = [e[0] for e in spans
                      if outer[1] <= e[1] and e[2] <= outer[2]
                      and e is not outer]
            assert set(inside) == set(stages), (outer, inside)
        # every stage lies inside the prefill or one step
        for e in spans:
            if e[0] in stages:
                assert any(o[1] <= e[1] and e[2] <= o[2]
                           for o in [pre] + steps), e

    @pytest.mark.parametrize("chunk", [None, 4], ids=["mono", "chunk4"])
    @pytest.mark.parametrize("cut", ["0", "1", "L"])
    def test_stage_sequence(self, lm, monkeypatch, cut, chunk):
        """The ordered stages of the prefill and of two decode steps: the
        spans entered (by their nesting path), the backend programs each
        dispatches and ``segment.skip``, at p = 0, 1 and L, with a
        monolithic prefill and with 4-token chunks (8 tokens: 2 chunks)."""
        b = _backend(lm)
        L = b.num_layers
        p = L if cut == "L" else int(cut)
        events, stack = [], []

        @contextlib.contextmanager
        def record(name, **args):
            stack.append(name)
            events.append("/".join(stack))
            try:
                yield types.SimpleNamespace(set_metadata=lambda **kw: None)
            finally:
                stack.pop()

        def wrap(name):
            fn = getattr(b, name)

            def call(*a, **kw):
                events.append("/".join(stack) + ":" + name)
                return fn(*a, **kw)
            return call

        monkeypatch.setattr(pipeline, "span", record)
        for name in ("embed", "extend_segment", "decode_segment",
                     "prefill_segment", "hidden_logits"):
            monkeypatch.setattr(b, name, wrap(name))
        DecodeSession(b, _plan(p), max_len=MAX_LEN,
                      prefill_chunk_tokens=chunk).generate(
            np.zeros((1, 8), np.int32), 3)

        def stages(outer, program, rounds):
            out = [outer]
            for _ in range(rounds):
                if p > 0:
                    out += [f"{outer}/device", f"{outer}/device:embed",
                            f"{outer}/device:{program}", f"{outer}/hop",
                            f"{outer}/fence"]
                if p < L:
                    out.append(f"{outer}/server")
                    if p == 0:
                        out.append(f"{outer}/server:embed")
                    out.append(f"{outer}/server:{program}")
                    if outer == "prefill":
                        out.append("prefill/fence")
            return out + [f"{outer}/unembed", f"{outer}/unembed:hidden_logits",
                          f"{outer}/sync"]

        chunks = 1 if chunk is None else 2
        want = (stages("prefill", "extend_segment", chunks)
                + 2 * stages("step", "decode_segment", 1))
        assert events == want
        assert b.counters["segment.skip"] == (chunks + 2 if p == L else 0)

    def test_offload_steps_have_no_device_stages(self, lm, tmp_path):
        b = _backend(lm)
        prompt = np.zeros((1, 8), np.int32)
        DecodeSession(b, _plan(0), max_len=MAX_LEN).generate(prompt, 3)
        with jax.profiler.trace(str(tmp_path)):
            DecodeSession(b, _plan(0), max_len=MAX_LEN).generate(prompt, 3)
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        names = [e[0] for e in _program_spans(path)]
        assert names.count("prefill") == 1 and names.count("step") == 2
        assert not {"device", "hop", "split", "stack"} & set(names)
        assert names.count("server") == 3 and names.count("sync") == 3


@pytest.fixture(scope="module")
def moonlight():
    """Moonlight's toy stand-in (``bench/families/mla_moe.py``) in float32:
    a dense layer and three expert layers, 4 of 8 experts held."""
    from bench.core import program, spec
    from bench.core.weights import make_program_params
    family = spec.family_module("mla_moe")
    m = family.rehearsal(spec.load_cell("moonlight-16b-a3b.edge").model,
                         "toy")
    cfg = dataclasses.replace(program.program_config(family, m),
                              dtype="float32")
    params = make_program_params(11, m, cfg, family)
    return family, m, TransformerBackend(cfg, params, seq_len=SEQ,
                                         decode_max_len=MAX_LEN)


class TestMoECounters:
    def test_counters_equal_the_reference_routing(self, moonlight):
        """moe.pairs / pairs_here / experts_active after one offloaded
        request (p = 0: the full-precision server, as the reference
        computes it) equal the routing the reference computes over the
        same sequence: k pairs per token and expert layer; pairs on the
        held experts (ids 2-5); held experts with a pair, per dispatch
        (the prefill, then each decode step's one token)."""
        family, m, b = moonlight
        n = family.dims(m)
        prompt = (np.arange(8, dtype=np.int32) * 29 + 3) % n["V"]
        res = DecodeSession(b, _plan(0), max_len=MAX_LEN).generate(
            prompt[None], 4)
        seq = np.concatenate([prompt, res.tokens[0, :-1]])
        ref = family.Reference(m, 11, 16, 16)
        routes = ref.routes(seq, np.arange(len(seq)), 0, [], 0)
        held = np.arange(n["first_held"], n["first_held"] + n["held"])
        pairs = here = active = 0
        for ids in routes.values():
            on = np.isin(ids, held)
            pairs += ids.size
            here += int(on.sum())
            dispatches = [ids[:8]] + [ids[i:i + 1] for i in range(8, 11)]
            active += sum(len(set(d[np.isin(d, held)].tolist()))
                          for d in dispatches)
        c = b.counters
        assert pairs == 11 * n["k"] * (n["L"] - n["dense"])
        assert (c["moe.pairs"], c["moe.pairs_here"],
                c["moe.experts_active"]) == (pairs, here, active)

    def test_counters_are_read_once_per_request(self, moonlight, tmp_path):
        """The counters ride through every prefill and step program on
        the device; one ``qpart.moe`` span per request reads them, after
        the last step and outside every step, its arguments the
        request's totals and the per-layer rows."""
        _, _, b = moonlight
        prompt = np.zeros((1, 8), np.int32)
        plan = _plan(b.num_layers)
        DecodeSession(b, plan, max_len=MAX_LEN).generate(prompt, 4)
        before = dict(b.counters)
        with jax.profiler.trace(str(tmp_path)):
            DecodeSession(b, plan, max_len=MAX_LEN).generate(prompt, 4)
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        spans = _program_spans(path)
        (moe,) = [e for e in spans if e[0] == "moe"]
        steps = [e for e in spans if e[0] == "step"]
        assert len(steps) == 3 and moe[1] >= steps[-1][2]
        args = moe[3]
        for name in ("pairs", "pairs_here", "experts_active"):
            assert args[name] == b.counters["moe." + name] - \
                before["moe." + name]
        by_layer = [int(v) for v in args["experts_active_by_layer"]
                    .split()]
        assert len(by_layer) == b.num_layers and by_layer[0] == 0
        assert sum(by_layer) == args["experts_active"]
