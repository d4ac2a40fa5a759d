"""Plan → deploy → execute: the objects the online pipeline hands out.

``QPARTServer`` keys its offline stores by a ``ReferenceContext`` (the
device/channel/weights Alg. 1 optimized for) and its online entry points
(``serve`` / ``serve_batch`` / ``WorkloadBalancer.schedule``) return a
``Deployment``: the chosen plan, its priced costs, and a callable
quantized device segment — with measurement (really running the
partitioned, quantized model on a test set) an explicit separate step,
``Deployment.execute``, instead of an optional side effect of serving.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.cost_model import (Channel, DeviceProfile, ObjectiveWeights)
from repro.core.solver import PartitionPlan
from repro.serving.backends.base import DeviceExecutor, ModelBackend
from repro.serving.simulator import InferenceRequest, ServingResult
from repro.serving.tracing import span


@dataclasses.dataclass(frozen=True)
class ReferenceContext:
    """The (device, channel, weights) a pattern store was built against
    (Alg. 1's reference request). Hashable — all three profiles are frozen
    dataclasses — so one model holds stores for many contexts side by
    side instead of each ``build_store`` overwriting the last."""
    device: DeviceProfile
    channel: Channel
    weights: ObjectiveWeights


@dataclasses.dataclass
class Deployment:
    """One served request: the plan Alg. 2 picked, its priced costs, and
    the means to really run it. Cheap to create — the device segment is
    split on first ``device_segment()``/``execute``, and its quantized
    weights are built only when a reader needs them, so neither the
    batched pricing paths nor the kernel path pay for quantization."""
    model: str
    backend: ModelBackend
    request: InferenceRequest
    plan: PartitionPlan
    result: ServingResult
    _segment: Optional[DeviceExecutor] = dataclasses.field(
        default=None, repr=False, compare=False)

    # -- convenience views over the priced result -----------------------
    @property
    def costs(self):
        return self.result.costs

    @property
    def objective(self) -> float:
        return self.result.objective

    @property
    def payload_bits(self) -> float:
        return self.result.payload_bits

    @property
    def extra(self) -> dict:
        return self.result.extra

    @property
    def queue_delay(self) -> float:
        """Server queue delay priced into this deployment's objective —
        0.0 on the queue-less paths (``serve``/``serve_batch``)."""
        return self.result.extra.get("queue_delay", 0.0)

    @property
    def accuracy(self):
        return self.result.accuracy

    @property
    def accuracy_degradation(self):
        return self.result.accuracy_degradation

    # -- deploy ---------------------------------------------------------
    def device_segment(self) -> DeviceExecutor:
        """The callable quantized device segment (split on first call):
        maps a raw input batch to the quantized cut activation the device
        would uplink. Cached — repeated execute calls quantize once."""
        if self._segment is None:
            with span("split", p=int(self.plan.p)):
                self._segment = self.backend.device_executor(self.plan)
        return self._segment

    # -- execute --------------------------------------------------------
    def execute(self, test_x, test_y) -> ServingResult:
        """Really run the partitioned, quantized model on (test_x,
        test_y): quantized device segment, quantized cut activation,
        full-precision server tail. Fills ``result.accuracy`` and
        ``result.accuracy_degradation`` (vs the full-precision model on
        the SAME test set) and returns the result.

        The two compute stages are wall-clock fenced
        (``jax.block_until_ready`` between them) and recorded into
        ``result.extra['measured']`` alongside the predicted breakdown
        (``result.costs``), so predicted-vs-measured fidelity is
        inspectable on every executed deployment — and feedable into
        ``QPARTServer.record_execution`` / the calibration ledger
        (DESIGN.md §9). First execution of a (p, shape) pays XLA
        compilation; re-execute (the compile caches persist) before
        trusting the timings."""
        t0 = time.perf_counter()
        if self.plan.p:
            h = jax.block_until_ready(self.device_segment()(test_x))
            t1 = time.perf_counter()
            logits = jax.block_until_ready(
                self.backend.forward_from_layer(h, self.plan.p))
        else:
            t1 = t0
            logits = jax.block_until_ready(self.backend.forward(test_x))
        t2 = time.perf_counter()
        self.result.extra["measured"] = {
            "batch": int(test_x.shape[0]),
            "t_device_s": t1 - t0,
            "t_server_s": t2 - t1,
            "t_total_s": t2 - t0,
            # the prediction the same stages were priced at (provider
            # breakdown; radio time excluded — nothing is transmitted)
            "t_device_pred_s": self.result.costs.t_local,
            "t_server_pred_s": self.result.costs.t_server,
        }
        acc = float(jnp.mean(jnp.argmax(logits, -1) == test_y))
        # memoized per test-set identity on the backend: a window of
        # deployments executing against one test set pays for the
        # full-precision baseline forward once
        base = self.backend.evaluate(test_x, test_y)
        self.result.accuracy = acc
        self.result.accuracy_degradation = base - acc
        return self.result

    # -- generate (autoregressive decode, DESIGN.md §11) ----------------
    def decode_session(self, max_len: Optional[int] = None,
                       prefill_chunk_tokens: Optional[int] = None,
                       draft_tokens: int = 0):
        """A fresh ``DecodeSession`` on this deployment's plan, reusing
        the lazily-materialized quantized device segment. The serving-
        shape knobs (DESIGN.md §14) pass through: ``prefill_chunk_tokens``
        admits the prompt in chunks, ``draft_tokens`` turns decode rounds
        speculative — both bit-identical to the plain pipeline."""
        from repro.serving.decode import DecodeSession
        seg = self.device_segment().segment if self.plan.p else None
        if max_len is None:
            max_len = getattr(self.backend, "decode_max_len", None) \
                or 2 * getattr(self.backend, "seq_len", 1)
        return DecodeSession(self.backend, self.plan, max_len=max_len,
                             segment=seg,
                             prefill_chunk_tokens=prefill_chunk_tokens,
                             draft_tokens=draft_tokens)

    def generate(self, prompt, max_new_tokens: int, *,
                 max_len: Optional[int] = None, stream_cb=None,
                 prefill_chunk_tokens: Optional[int] = None,
                 draft_tokens: int = 0):
        """Stream ``max_new_tokens`` greedy tokens through the
        partitioned prefill→decode pipeline (quantized device segment
        ``[0, p)`` with its cache at the deployed bit-width's dtype,
        full-precision server tail ``[p, L)``). Wall-clock stage seconds
        land in ``result.extra['measured_decode']`` — the sample
        ``CalibrationLedger.record_decode`` regresses per-token rates
        from. ``stream_cb(i, token)`` observes tokens as they decode.
        Returns a ``decode.GenerationResult``."""
        sess = self.decode_session(max_len=max_len,
                                   prefill_chunk_tokens=prefill_chunk_tokens,
                                   draft_tokens=draft_tokens)
        out = sess.generate(prompt, max_new_tokens, stream_cb=stream_cb)
        self.result.extra["measured_decode"] = {
            "batch": int(out.tokens.shape[0]),
            "new_tokens": out.new_tokens,
            "ttft_s": out.ttft_s,
            "t_device_s": out.t_device_s,
            "t_server_s": out.t_server_s,
            "t_total_s": out.t_total_s,
            "tokens_per_s": out.tokens_per_s,
            "device_cache_bytes": out.device_cache_bytes,
            "device_cache_dtype": out.device_cache_dtype,
            # serving-shape measurements (DESIGN.md §14): rounds counts
            # decode rounds; accept_rate is the measured draft
            # acceptance the CalibrationLedger feeds back into the
            # expected-tokens-per-round pricing term (None = no drafts)
            "rounds": out.rounds,
            "draft_tokens": out.draft_tokens,
            "drafts_proposed": out.drafts_proposed,
            "drafts_accepted": out.drafts_accepted,
            "accept_rate": out.accept_rate,
            "prefill_chunks": out.prefill_chunks,
        }
        return out
