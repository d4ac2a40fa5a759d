"""Tests for the model-agnostic serving API: the ``ModelBackend``
protocol, a decoder transformer through the full QPART pipeline,
multi-context stores, plan-time device-memory enforcement, and the
``ServingError`` hierarchy."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.configs.classifier import MNIST_MLP
from repro.core.cost_model import Channel, DeviceProfile, ObjectiveWeights
from repro.core.partition import plan_memory_bytes, segment_memory_bytes
from repro.core.quantizer import fake_quant, payload_bits
from repro.core.solver import PartitionPlan
from repro.models import transformer as T
from repro.models.classifier import init_classifier
from repro.serving.backends import ClassifierBackend, TransformerBackend
from repro.serving.deployment import Deployment, ReferenceContext
from repro.serving.errors import (NotCalibratedError, ServingError,
                                  StoreMissingError, UnknownModelError)
from repro.serving.qpart_server import QPARTServer
from repro.serving.simulator import InferenceRequest

SEQ = 16


def tiny_lm_config():
    return dataclasses.replace(
        get_config("smollm-135m").reduced(), name="smollm-tiny",
        d_model=64, num_heads=2, num_kv_heads=1, head_dim=32, d_ff=128,
        vocab_size=32, tp_pad=1, dtype="float32")


def cycle_batch(rng, cfg, n):
    """Deterministic next-token task: t[i+1] = (t[i] + 1) mod V. x is the
    (B, SEQ) prompt, y the next token after the last position."""
    start = rng.integers(0, cfg.vocab_size, size=(n, 1))
    toks = (start + np.arange(SEQ + 1)[None, :]) % cfg.vocab_size
    return (jnp.asarray(toks[:, :SEQ], jnp.int32),
            jnp.asarray(toks[:, SEQ], jnp.int32))


@pytest.fixture(scope="module")
def trained_lm():
    cfg = tiny_lm_config()
    params = T.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)

    def loss_fn(p, toks):
        logits, _ = T.forward(p, cfg, toks[:, :-1])
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, toks[:, 1:][..., None], -1))

    @jax.jit
    def step(p, toks):
        _, g = jax.value_and_grad(loss_fn)(p, toks)
        return jax.tree.map(lambda a, b: a - 0.5 * b, p, g)

    for _ in range(300):
        start = rng.integers(0, cfg.vocab_size, size=(32, 1))
        toks = jnp.asarray((start + np.arange(SEQ + 1)[None, :])
                           % cfg.vocab_size, jnp.int32)
        params = step(params, toks)
    return cfg, params, rng


@pytest.fixture(scope="module")
def lm_served(trained_lm):
    cfg, params, rng = trained_lm
    backend = TransformerBackend(cfg, params, seq_len=SEQ)
    x_cal, y_cal = cycle_batch(rng, cfg, 128)
    srv = QPARTServer()
    srv.register("smollm", backend, x_cal, y_cal)
    srv.calibrate("smollm")
    dev, ch, w = DeviceProfile(), Channel(capacity_bps=2e6), ObjectiveWeights()
    srv.build_store("smollm", dev, ch, w)
    return srv, backend, (dev, ch, w)


class TestTransformerBackend:
    def test_forward_matches_scan_forward(self, trained_lm):
        """The backend's block-by-block forward is the same math as the
        production lax.scan forward."""
        cfg, params, rng = trained_lm
        backend = TransformerBackend(cfg, params, seq_len=SEQ)
        x, _ = cycle_batch(rng, cfg, 8)
        ref, _ = T.forward(params, cfg, x)
        np.testing.assert_allclose(np.asarray(backend.forward(x)),
                                   np.asarray(ref[:, -1, :]),
                                   rtol=1e-4, atol=1e-5)

    def test_layer_specs_drop_embed_row(self, trained_lm):
        cfg, params, _ = trained_lm
        backend = TransformerBackend(cfg, params, seq_len=SEQ)
        specs = backend.layer_specs()
        assert len(specs) == cfg.num_layers == backend.num_layers
        assert all(sp.o > 0 for sp in specs)

    def test_e2e_calibrate_build_serve_execute(self, lm_served, trained_lm):
        """A decoder transformer runs the FULL pipeline: calibrate →
        build_store → serve → Deployment.execute, with measured accuracy
        degradation reported."""
        cfg, params, rng = trained_lm
        srv, backend, (dev, ch, w) = lm_served
        m = srv.models["smollm"]
        assert m.base_accuracy > 0.9          # the cycle task is learnable
        assert np.all(m.s_w > 0) and np.all(m.rho > 0)
        x_te, y_te = cycle_batch(rng, cfg, 96)
        dep = srv.serve(InferenceRequest("smollm", 0.01, dev, ch, w,
                                         segment_cached=True))
        assert isinstance(dep, Deployment)
        res = dep.execute(x_te, y_te)
        assert res.accuracy is not None
        assert res.accuracy_degradation is not None
        assert res.objective > 0

    def test_quantized_partitioned_execution(self, lm_served, trained_lm):
        """Force the all-blocks-on-device plan: quantized blocks + a
        quantized cut activation + fp server tail really execute, and the
        quantized payload beats f32."""
        cfg, params, rng = trained_lm
        srv, backend, _ = lm_served
        m = srv.models["smollm"]
        L = cfg.num_layers
        plan = m.store().plans[(0.02, L)]
        specs = backend.layer_specs()
        assert plan.payload_bits < sum(sp.z_w for sp in specs) * 32.0
        x_te, y_te = cycle_batch(rng, cfg, 96)
        acc = srv.execute_partitioned("smollm", plan, x_te, y_te)
        assert 0.0 <= acc <= 1.0
        # the quantized model retains most of the (perfect) base accuracy
        assert acc > 0.5

    def test_segment_memory_matches_plan(self, lm_served):
        srv, backend, _ = lm_served
        m = srv.models["smollm"]
        plan = m.store().plans[(0.01, backend.num_layers)]
        seg = backend.split(plan)
        # analytic plan-time footprint vs the materialized segment: the
        # plan uses the cost-model z_w (analytic block params), the
        # segment counts real leaves — they agree within the small
        # analytic/actual param-count gap (A_log/D scalars etc.)
        assert segment_memory_bytes(seg) == pytest.approx(
            plan.device_memory_bytes, rel=0.05)
        assert plan_memory_bytes(plan, backend.layer_specs()) \
            == pytest.approx(plan.device_memory_bytes, rel=1e-9)


def _lazy_split_case(kind):
    """(backend factory over a params tree, its params, plan) for a
    SmolLM-shaped decoder cut inside the stack or the MNIST MLP. The bits
    include one a hair above an integer, where rounding in float32 and
    float64 disagree."""
    key = jax.random.key(0)
    if kind == "smollm":
        cfg = dataclasses.replace(tiny_lm_config(), num_layers=4)
        params = T.init_params(key, cfg)
        make = functools.partial(TransformerBackend, cfg, seq_len=SEQ)
        bits = [2.5, 5.0 + 1e-9, 7.999]
    else:
        params = init_classifier(key, MNIST_MLP)
        make = functools.partial(ClassifierBackend, MNIST_MLP)
        bits = [3.2, 8.0, 5.0 + 1e-9, 2.0]
    plan = PartitionPlan(p=len(bits), bits_w=np.array(bits), bits_x=6.0000001,
                         objective=0.0, psi_total=0.0, payload_bits=0.0,
                         breakdown={})
    return make, params, plan


def _eager_split(backend, plan):
    """The split as computed before the weights became lazy: every device
    layer sliced and fake-quantized up front, the bits rounded by jnp, the
    sizes counted from the quantized leaves. -> (params, bits_w, bits_x,
    payload bits, memory bytes)."""
    def rb(b):
        return np.asarray(
            jnp.clip(jnp.ceil(jnp.asarray(b)), 2, 16).astype(jnp.int32))

    bits = rb(plan.bits_w)
    if isinstance(backend, TransformerBackend):
        layers = [T.block_at(backend.params, backend.cfg, l)[0]
                  for l in range(plan.p)]
    else:
        layers = backend.params[:plan.p]
    params = [jax.tree.map(lambda t, b=int(b): fake_quant(t, b), lp)
              for lp, b in zip(layers, bits)]
    wire = mem = 0.0
    for lp, b in zip(params, bits):
        n = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(lp))
        wire += float(payload_bits(n, int(b)))
        mem += n * int(b) / 8.0
    bits_x = int(rb(np.array([plan.bits_x]))[0])
    z_x = backend.layer_specs()[plan.p - 1].z_x
    wire += float(payload_bits(int(z_x), bits_x))
    return params, bits, bits_x, wire, mem


class TestLazySplit:
    @pytest.mark.parametrize("kind", ["smollm", "mnist"])
    def test_split_defers_quantization_to_first_read(self, kind):
        make, params, plan = _lazy_split_case(kind)
        backend = make(params)
        want, bits, bits_x, wire, mem = _eager_split(backend, plan)
        ex = backend.device_executor(plan)
        seg = ex.segment
        assert backend.counters["split.materialize"] == 0
        np.testing.assert_array_equal(seg.bits_w, bits)
        assert seg.bits_x == bits_x
        assert seg.payload_bits == ex.payload_bits == wire
        assert segment_memory_bytes(seg) == ex.memory_bytes == mem
        # the bookkeeping reads shapes only: a split over abstract
        # params gives the same numbers without touching any data
        abstract = make(jax.eval_shape(lambda: params)).split(plan)
        assert (abstract.payload_bits, abstract.layer_elements) == \
            (seg.payload_bits, seg.layer_elements)
        got = seg.params
        assert backend.counters["split.materialize"] == 1
        assert len(got) == plan.p
        for g, w in zip(got, want):
            assert jax.tree.structure(g) == jax.tree.structure(w)
            for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
                assert a.dtype == b.dtype
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert seg.params is got
        assert backend.counters["split.materialize"] == 1


class TestMultiContextStores:
    def test_stores_accumulate_per_context(self, lm_served):
        srv, backend, (dev, ch, w) = lm_served
        m = srv.models["smollm"]
        n_before = len(m.stores)
        ch2 = Channel(capacity_bps=100e6)
        ctx2 = srv.build_store("smollm", dev, ch2, w)
        assert len(m.stores) == n_before + 1
        assert m.store(ctx2) is m.stores[ctx2]
        # the first context's store is still addressable
        ctx1 = ReferenceContext(dev, ch, w)
        assert m.store(ctx1) is not m.store(ctx2)
        # default follows the most recent build (old overwrite semantics)
        assert m.default_context == ctx2
        # serving against an explicit context picks that store's plans
        req = InferenceRequest("smollm", 0.01, dev, ch, w)
        dep1 = srv.serve(req, context=ctx1)
        assert any(dep1.plan is pl for pl in m.store(ctx1).plans.values())
        # restore default for other tests
        srv.build_store("smollm", dev, ch, w)

    def test_missing_context_raises(self, lm_served):
        srv, backend, (dev, ch, w) = lm_served
        ghost = ReferenceContext(dev, Channel(capacity_bps=123.0), w)
        with pytest.raises(StoreMissingError):
            srv.serve(InferenceRequest("smollm", 0.01, dev, ch, w),
                      context=ghost)


class TestMemoryEnforcement:
    @pytest.fixture(scope="class")
    def served(self):
        """Pricing-only classifier server (fabricated calibration)."""
        srv = QPARTServer()
        x = np.zeros((4, 28, 28), np.float32)
        y = np.zeros(4, np.int32)
        srv.register("mnist", ClassifierBackend(MNIST_MLP, None), x, y)
        m = srv.models["mnist"]
        L = MNIST_MLP.num_layers
        m.s_w = np.ones(L)
        m.s_x = np.ones(L)
        m.rho = np.full(L, 0.1)
        m.delta_table = {a: a * 50 for a in srv.levels}
        dev = DeviceProfile()
        ch = Channel(capacity_bps=2e6)
        w = ObjectiveWeights()
        srv.build_store("mnist", dev, ch, w)
        return srv, dev, ch, w

    def test_infeasible_candidates_rejected(self, served):
        srv, dev, ch, w = served
        m = srv.models["mnist"]
        store = m.store()
        # unconstrained choice keeps layers on-device (congested uplink)
        req = InferenceRequest("mnist", 0.01, dev, ch, w,
                               segment_cached=True)
        p_free = srv.serve(req).plan.p
        assert p_free > 0
        # a device too small for ANY quantized segment: only p=0 fits
        tiny = dataclasses.replace(dev, memory_bytes=10.0)
        dep = srv.serve(InferenceRequest("mnist", 0.01, tiny, ch, w,
                                         segment_cached=True))
        assert dep.plan.p == 0
        # a mid-size budget: the chosen segment must fit it
        lv = store.level_for(0.01)
        mems = store.level_memory_rows(lv)
        cap = float(np.sort(mems[mems > 0])[0]) * 1.5
        mid = dataclasses.replace(dev, memory_bytes=cap)
        dep2 = srv.serve(InferenceRequest("mnist", 0.01, mid, ch, w,
                                          segment_cached=True))
        assert 0 < dep2.plan.device_memory_bytes <= cap or dep2.plan.p == 0

    def test_batch_matches_scalar_under_memory_pressure(self, served):
        srv, dev, ch, w = served
        tiny = dataclasses.replace(dev, memory_bytes=10.0)
        mid = dataclasses.replace(dev, memory_bytes=300e3)
        reqs = [InferenceRequest("mnist", 0.01,
                                 (dev, tiny, mid)[i % 3], ch, w,
                                 segment_cached=True) for i in range(9)]
        batch = srv.serve_batch(reqs)
        for req, br in zip(reqs, batch):
            sr = srv.serve(req)
            assert br.plan is sr.plan
            assert br.objective == pytest.approx(sr.objective, rel=1e-12)
            assert br.plan.device_memory_bytes <= req.device.memory_bytes

    def test_scheduler_respects_memory(self, served):
        from repro.serving.scheduler import WorkloadBalancer
        from repro.core.cost_model import ServerProfile
        srv, dev, ch, w = served
        tiny = dataclasses.replace(dev, memory_bytes=10.0)
        reqs = [InferenceRequest("mnist", 0.01, tiny, ch, w,
                                 segment_cached=True) for _ in range(4)]
        out = WorkloadBalancer(ServerProfile()).schedule(srv, reqs)
        assert all(sr.deployment.plan.p == 0 for sr in out)


class TestServingErrors:
    def test_unknown_model(self):
        srv = QPARTServer()
        req = InferenceRequest("ghost", 0.01, DeviceProfile(), Channel())
        with pytest.raises(UnknownModelError):
            srv.serve(req)
        with pytest.raises(ServingError):       # one catchable root
            srv.serve_batch([req])
        with pytest.raises(UnknownModelError):
            srv.calibrate("ghost")

    def test_uncalibrated_model(self):
        srv = QPARTServer()
        srv.register("mnist", ClassifierBackend(
            MNIST_MLP, init_classifier(jax.random.key(0), MNIST_MLP)),
            np.zeros((4, 28, 28), np.float32), np.zeros(4, np.int32))
        req = InferenceRequest("mnist", 0.01, DeviceProfile(), Channel())
        with pytest.raises(NotCalibratedError):
            srv.serve(req)
        with pytest.raises(NotCalibratedError):
            srv.serve_batch([req])
        with pytest.raises(NotCalibratedError):
            srv.build_store("mnist", DeviceProfile(), Channel(),
                            ObjectiveWeights())

    def test_errors_are_serving_errors(self):
        assert issubclass(UnknownModelError, ServingError)
        assert issubclass(NotCalibratedError, ServingError)
        assert issubclass(StoreMissingError, ServingError)
