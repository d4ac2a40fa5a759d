"""Set-up of the system under test: the program's model, server, offline
stores and warmed programs for one cell, built through the program's own
entry points (register -> calibrate -> build_store -> serve).

Calibration is QPART's offline phase (Alg. 1) and belongs to set-up, as
do the held-out accuracy of each distinct plan and the warm-up of every
shape the window uses: each prompt bucket under each distinct plan.
"""
from __future__ import annotations

import collections
import dataclasses
import time

import numpy as np

from bench.core.weights import make_program_params

# accuracy levels of the offline store (Alg. 1); the traffic files'
# budgets pick among them
LEVELS = (0.001, 0.0025, 0.005, 0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 0.8, 0.95,
          0.99)


class RegimeError(RuntimeError):
    """The planner's plans leave the regime the traffic file declares."""


def program_config(family, model: dict):
    """The program's ModelConfig carrying the config file's sizes (the
    family's ``program_fields``); every other field keeps the program's
    default."""
    from repro.configs.base import get_config
    base = get_config(model["program_config"])
    return dataclasses.replace(base, name=model["name"],
                               **family.program_fields(model))


@dataclasses.dataclass
class Plan:
    p: int
    bits_w: tuple
    bits_x: int

    @property
    def key(self) -> tuple:
        return (self.p, self.bits_w, self.bits_x)


def plan_of(dep) -> Plan:
    bits = tuple(int(b) for b in dep.extra["bits_w"])
    bits_x = int(np.ceil(dep.plan.bits_x)) if dep.plan.p else 0
    return Plan(int(dep.plan.p), bits, max(min(bits_x, 16), 2)
                if dep.plan.p else 0)


@dataclasses.dataclass
class System:
    cfg: object
    traffic: dict
    srv: object
    backend: object
    contexts: list          # (ReferenceContext, DeviceProfile, Channel,
    #                          ObjectiveWeights, budget) per traffic context
    plans: dict             # context index -> Plan
    accuracy: dict          # plan key -> realized accuracy degradation
    max_len: int

    def request(self, ctx: int, max_new_tokens: int):
        from repro.serving.simulator import InferenceRequest
        rc, dev, ch, w, budget = self.contexts[ctx]
        # batch 1: the driver streams one sequence per request
        return InferenceRequest(
            self.cfg.name, budget, dev, ch, w, batch=1,
            segment_cached=bool(self.traffic["segment_cached"]),
            max_new_tokens=max_new_tokens)

    def serve(self, ctx: int, max_new_tokens: int):
        return self.srv.serve(self.request(ctx, max_new_tokens),
                              self.contexts[ctx][0])


def _check_regime(traffic: dict, plan: Plan, L: int, weights: str,
                  kernel_lane: bool) -> None:
    """``offload``: every plan p = 0. ``device_all``: every plan p = L,
    the whole stack on the device, served as wire structs through the
    dequantize-fused kernels (``weights`` int8 or int4). ``qstacked_for``
    falls back to dense weights for a plan with a layer above 8 bits; the
    traffic's budgets are chosen so that no seed plans one (PERF.md).
    Wire structs exist only in the compiled-kernel lane, the one the
    measuring path runs; a CPU rehearsal's device weights are dense."""
    regime = traffic["regime"]
    if regime == "offload":
        ok = plan.p == 0
    elif regime == "device_all":
        ok = plan.p == L and (weights in ("int8", "int4")
                              or not kernel_lane)
    else:
        raise RegimeError(f"unknown regime {regime!r}")
    if not ok:
        raise RegimeError(f"plan p={plan.p} bits_w={plan.bits_w} "
                          f"bits_x={plan.bits_x} device weights={weights} "
                          f"is outside the traffic's declared regime "
                          f"{regime!r}")


class Phases:
    """Host seconds of each set-up phase, logged as it ends."""

    def __init__(self, log):
        self.log = log
        self.t = time.perf_counter()

    def done(self, name: str) -> None:
        now = time.perf_counter()
        self.log(f"[setup] {name} {now - self.t:.3f} s")
        self.t = now


def build(family, model: dict, traffic: dict, seed: int, log) -> System:
    """Everything up to the warm-up, for a model of ``family``."""
    import jax
    import jax.numpy as jnp

    from repro.core.cost_model import Channel, DeviceProfile, ObjectiveWeights
    from repro.kernels import ops
    from repro.serving.backends import TransformerBackend
    from repro.serving.decode import DecodeSession
    from repro.serving.qpart_server import QPARTServer

    ph = Phases(log)
    cfg = program_config(family, model)
    st = model["setup"]
    params = make_program_params(seed, model, cfg, family)
    ph.done("weights")
    rng = np.random.default_rng([seed, 1])
    n_c, n_t = st["calib_sequences"], st["test_sequences"]
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                    (n_c + n_t, st["calib_len"])), jnp.int32)
    calib_x, test_x = toks[:n_c], toks[n_c:]
    backend = TransformerBackend(cfg, params, seq_len=st["calib_len"],
                                 decode_max_len=st["decode_max_len"])
    # labels: the full-precision model's own greedy next tokens, so a
    # plan's accuracy is its top-1 agreement with the unquantized model
    calib_y = jnp.argmax(backend.forward(calib_x), -1)
    test_y = jnp.argmax(backend.forward(test_x), -1)
    jax.block_until_ready(test_y)
    ph.done("labels")
    srv = QPARTServer(levels=LEVELS)
    srv.register(cfg.name, backend, calib_x, calib_y)
    srv.calibrate(cfg.name)
    ph.done("calibrate")
    contexts = []
    for c in traffic["contexts"]:
        dev = DeviceProfile(**c["device"])
        ch = Channel(**c["channel"])
        w = ObjectiveWeights(**c["weights"])
        rc = srv.build_store(cfg.name, dev, ch, w)
        contexts.append((rc, dev, ch, w, float(c["budget"])))
    ph.done("build_store")
    system = System(cfg, traffic, srv, backend, contexts, {}, {},
                    int(st["decode_max_len"]))
    for i in range(len(contexts)):
        dep = system.serve(i, 1)
        plan = system.plans[i] = plan_of(dep)
        if plan.key not in system.accuracy:
            res = dep.execute(test_x, test_y)
            system.accuracy[plan.key] = float(res.accuracy_degradation)
            sess = DecodeSession(backend, dep.plan, max_len=system.max_len,
                                 segment=dep.device_segment().segment
                                 if plan.p else None)
            kind = "none" if sess.dev_params is None \
                else family.weights_kind(sess.dev_params)
            log(f"[plan] context {i}: p={plan.p} bits_w={list(plan.bits_w)} "
                f"bits_x={plan.bits_x} device weights={kind} "
                f"accuracy_degradation={system.accuracy[plan.key]:.6f}")
            _check_regime(traffic, plan, cfg.num_layers, kind,
                          ops.kernel_mode() == "kernel")
    ph.done("plans and held-out accuracy")
    return system


def warm_up(system: System, seed: int) -> int:
    """Run every prompt bucket under every distinct plan through the
    window's own entry (serve -> generate, two tokens: the prefill and
    one decode step). One deployment per plan: its device segment is
    quantized once and reused across the buckets. Returns the number of
    warm-up generations."""
    rng = np.random.default_rng([seed, 2])
    seen, n = set(), 0
    for i in range(len(system.contexts)):
        key = system.plans[i].key
        if key in seen:
            continue
        seen.add(key)
        dep = system.serve(i, 2)
        for s in system.traffic["prompt_len"]["buckets"]:
            prompt = rng.integers(0, system.cfg.vocab_size, (1, s),
                                  dtype=np.int32)
            dep.generate(prompt, 2, max_len=system.max_len,
                         stream_cb=lambda j, t: None)
            n += 1
    return n


def plan_histogram(plans) -> dict:
    """'p=<p> bits=<sorted distinct bits>' -> requests."""
    return dict(collections.Counter(
        f"p={pl.p} bits_w={sorted(set(pl.bits_w))} bits_x={pl.bits_x}"
        for pl in plans))
