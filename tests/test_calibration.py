"""Compile-once partitioned execution + vectorized Alg. 1 calibration
(ISSUE 3): the masked segment forward matches the production scan forward
and the old per-start semantics at EVERY resume point, the vectorized
probes are regression-locked against the scalar reference loop in
``core.noise``, and the forward family's XLA compile count is O(1) in
depth (asserted via the backends' trace counter)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.configs.classifier import MNIST_MLP
from repro.core import noise as noise_lib
from repro.core.solver import PartitionPlan
from repro.models import transformer as T
from repro.models.classifier import init_classifier
from repro.serving.backends import ClassifierBackend, TransformerBackend
from repro.serving.qpart_server import QPARTServer

SEQ = 12
BATCH = 6


def lm_config(L: int = 4):
    # keep in sync with benchmarks/calibration_bench.py::_bench_cfg — the
    # bench measures the model these tests lock
    return dataclasses.replace(
        get_config("smollm-135m").reduced(), name=f"smollm-cal-L{L}",
        num_layers=L, d_model=64, num_heads=2, num_kv_heads=1, head_dim=32,
        d_ff=128, vocab_size=32, tp_pad=1, dtype="float32")


def tokens(rng, cfg, n):
    start = rng.integers(0, cfg.vocab_size, size=(n, 1))
    toks = (start + np.arange(SEQ + 1)[None, :]) % cfg.vocab_size
    return (jnp.asarray(toks[:, :SEQ], jnp.int32),
            jnp.asarray(toks[:, SEQ], jnp.int32))


def make_plan(p: int, bits: float = 8.0) -> PartitionPlan:
    return PartitionPlan(p, np.full(p, bits), bits, 1.0, 0.0, 0.0, {})


@pytest.fixture(scope="module")
def lm():
    cfg = lm_config()
    params = T.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    x, y = tokens(rng, cfg, BATCH)
    return cfg, params, x, y


@pytest.fixture(scope="module")
def mlp():
    params = init_classifier(jax.random.key(1), MNIST_MLP)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(32, 28, 28)).astype(np.float32))
    y = np.asarray(rng.integers(0, 10, 32))
    return params, x, y


class TestSegmentForward:
    def test_full_range_matches_scan_forward(self, lm):
        cfg, params, x, _ = lm
        ref, _ = T.forward(params, cfg, x)
        h = T.embed_tokens(params, cfg, x)
        out = T.segment_forward(params, cfg, h, 0, cfg.num_layers)
        got = T.unembed(params, cfg, out)[:, -1, :]
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref[:, -1, :]),
                                   rtol=1e-4, atol=1e-5)

    def test_every_start_matches_eager_blocks(self, lm):
        """segment_forward(start, stop) == the eager per-block loop over
        [start, stop) — the old per-start jit family's semantics — for
        EVERY window of a small stack, from one compiled program."""
        cfg, params, x, _ = lm
        L = cfg.num_layers
        from repro.models import rope as rope_lib
        h0 = T.embed_tokens(params, cfg, x)
        b, s, _ = h0.shape
        positions = rope_lib.text_positions(b, s)

        seg = jax.jit(lambda h, a, z: T.segment_forward(params, cfg, h, a, z))
        for start in range(L + 1):
            for stop in range(start, L + 1):
                ref = h0
                for l in range(start, stop):
                    bp, pos = T.block_at(params, cfg, l)
                    ref, _, _ = T.apply_block(bp, cfg, pos, ref, positions)
                got = seg(h0, start, stop)
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-5,
                    err_msg=f"window [{start}, {stop})")

    def test_collected_activations_match_layer_entries(self, lm):
        cfg, params, x, _ = lm
        backend = TransformerBackend(cfg, params, seq_len=SEQ)
        acts, logits = backend.layer_activations(x)
        assert len(acts) == cfg.num_layers
        # resuming at every collected activation reproduces the logits
        for l in range(cfg.num_layers):
            got = backend.forward_from_layer(acts[l], l)
            np.testing.assert_allclose(np.asarray(got), np.asarray(logits),
                                       rtol=1e-4, atol=1e-5)


class TestVectorizedProbes:
    """Vectorized ``calibrate_probes`` vs the scalar reference loop
    (``core.noise.backend_layer_energies``) — ISSUE 3's regression lock,
    on both backend families."""

    def test_transformer_probes_match_reference(self, lm):
        cfg, params, x, _ = lm
        backend = TransformerBackend(cfg, params, seq_len=SEQ)
        e_w_v, e_x_v, lg_v = backend.calibrate_probes(x)
        e_w_r, e_x_r, lg_r = noise_lib.backend_layer_energies(backend, x)
        np.testing.assert_allclose(e_w_v, e_w_r, rtol=2e-2)
        np.testing.assert_allclose(e_x_v, e_x_r, rtol=2e-2)
        np.testing.assert_allclose(np.asarray(lg_v), np.asarray(lg_r),
                                   rtol=1e-5, atol=1e-6)

    def test_classifier_probes_match_reference(self, mlp):
        params, x, _ = mlp
        backend = ClassifierBackend(MNIST_MLP, params)
        e_w_v, e_x_v, _ = backend.calibrate_probes(x)
        e_w_r, e_x_r, _ = noise_lib.backend_layer_energies(backend, x)
        np.testing.assert_allclose(e_w_v, e_w_r, rtol=2e-2)
        np.testing.assert_allclose(e_x_v, e_x_r, rtol=2e-2)

    def test_server_calibrate_vectorized_matches_scalar(self, lm):
        cfg, params, x, y = lm
        stats = {}
        for vectorized in (True, False):
            srv = QPARTServer()
            srv.register("lm", TransformerBackend(cfg, params, seq_len=SEQ),
                         x, y)
            srv.calibrate("lm", vectorized=vectorized)
            m = srv.models["lm"]
            stats[vectorized] = (m.s_w, m.s_x, m.rho)
        for v, r in zip(stats[True], stats[False]):
            np.testing.assert_allclose(v, r, rtol=2e-2)

    def test_probe_chunk_does_not_change_result(self, lm):
        """Chunk size is a memory/parallelism knob, not a semantic one."""
        cfg, params, x, _ = lm
        backend = TransformerBackend(cfg, params, seq_len=SEQ)
        e_w_1, e_x_1, _ = backend.calibrate_probes(x, chunk=1)
        e_w_3, e_x_3, _ = backend.calibrate_probes(x, chunk=3)
        np.testing.assert_allclose(e_w_1, e_w_3, rtol=1e-5)
        np.testing.assert_allclose(e_x_1, e_x_3, rtol=1e-5)


class TestCompileOnce:
    """The tentpole's acceptance: XLA compile count for the forward
    family is O(1) in depth. The backends count traces (the python body
    of a jitted function runs only when XLA traces)."""

    def _exercise(self, cfg, params, x):
        backend = TransformerBackend(cfg, params, seq_len=SEQ)
        backend.forward(x)
        acts, _ = backend.layer_activations(x)
        for l in range(cfg.num_layers):
            backend.forward_from_layer(acts[l], l)
        for p in range(1, cfg.num_layers + 1):
            backend.execute_plan(make_plan(p), x)
        return backend.trace_count

    def test_transformer_trace_count_depth_independent(self):
        counts = {}
        for L in (2, 6):
            cfg = lm_config(L)
            params = T.init_params(jax.random.key(0), cfg)
            x, _ = tokens(np.random.default_rng(0), cfg, BATCH)
            counts[L] = self._exercise(cfg, params, x)
        # every start and every partition point, from a handful of
        # programs — and the SAME handful at both depths
        assert counts[2] == counts[6] <= 4, counts

    def test_quantized_segment_execution_shares_cut_program(self, lm):
        """Deployments at different partition points share the cut
        program: executing every p adds at most ONE trace (the cut
        program's first compile)."""
        cfg, params, x, _ = lm
        backend = TransformerBackend(cfg, params, seq_len=SEQ)
        ref = backend.forward(x)
        before = backend.trace_count
        for p in range(1, cfg.num_layers + 1):
            logits = backend.execute_plan(make_plan(p, bits=16.0), x)
            assert logits.shape == ref.shape
        assert backend.trace_count <= before + 2
        # at generous bit-widths the partitioned model tracks the
        # full-precision one
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                                   rtol=0.1, atol=0.1)

    def test_classifier_segment_cache_keyed_by_p(self, mlp):
        params, x, _ = mlp
        backend = ClassifierBackend(MNIST_MLP, params)
        backend.forward(x)
        n0 = backend.trace_count
        for _ in range(3):          # repeat executions reuse compilations
            backend.execute_plan(make_plan(3), x)
        n1 = backend.trace_count
        assert n1 - n0 == 2         # one prefix(p=3) + one from_layer(3)
        backend.execute_plan(make_plan(3), x)
        assert backend.trace_count == n1


class TestEvaluateMemo:
    def test_evaluate_memoized_per_test_set_identity(self, mlp):
        params, x, y = mlp
        backend = ClassifierBackend(MNIST_MLP, params)
        calls = []
        orig = backend._measure

        def spy(xx, yy, prm):
            calls.append(1)
            return orig(xx, yy, prm)

        backend.__dict__["_measure"] = spy    # instance-level override
        a1 = backend.evaluate(x, y)
        a2 = backend.evaluate(x, y)
        assert a1 == a2 and len(calls) == 1   # identity hit
        x2 = jnp.asarray(np.asarray(x))       # equal values, new identity
        backend.evaluate(x2, y)
        assert len(calls) == 2
        # params override is never memoized
        backend.evaluate(x, y, params=params)
        assert len(calls) == 3


class TestDeltaSweep:
    """``calibrate_delta``'s psi sweep reaches the degradation targets:
    isotropic logit noise over V classes flips the argmax only far above
    psi*, so a large vocabulary extends the sweep past its base decade
    above psi*, while a few-class problem keeps the base grid."""

    @pytest.mark.parametrize("n_classes,targets", [
        (10, (0.005, 0.02)), (4096, (0.01, 0.5))], ids=["10", "4096"])
    def test_largest_target_is_reached(self, n_classes, targets):
        logits = jax.random.normal(jax.random.key(1), (32, n_classes))
        y = jnp.argmax(logits, -1)
        table, base = noise_lib.calibrate_delta(
            lambda p, x: x, None, logits, y, np.ones(4), targets)
        psi_star = float(jnp.mean(
            noise_lib.adversarial_noise_energy(logits)))
        assert base == 1.0
        assert table[targets[0]] <= table[targets[1]]
        if n_classes == 10:
            # degradation exceeds the targets inside the base grid
            assert table[targets[1]] <= 10 * psi_star
        else:
            assert table[targets[1]] > 10 * psi_star
