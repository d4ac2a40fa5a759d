"""The ``ModelBackend`` protocol: everything architecture-specific the
QPART serving pipeline needs, behind one interface (DESIGN.md §6).

The serving stack (``QPARTServer``, ``pricing``, ``scheduler``,
``baselines``) is model-agnostic: it speaks plans, costs and accuracy.
A backend owns the model family — its config, parameters, layer-spec
builder, forward functions and the quantized device-segment execution —
so a new architecture plugs into calibrate → build_store → serve by
implementing this class and nothing else.

Conventions shared by all backends:

  * "layers" are the partitionable units (classifier layers, decoder
    blocks). ``layer_specs()[l]`` describes layer ``l+1`` in the paper's
    1-indexed notation; a plan with ``p`` runs layers ``1..p`` on-device.
  * ``forward``-family methods return the logits the accuracy/noise
    calibration probes: shape (batch, num_classes) — for decoder LMs the
    next-token logits at the last position.
  * every forward method accepts a ``params`` override (default: the
    backend's own) so the calibration can probe perturbed weights and the
    baselines can run pruned ones without private model reach-ins.
  * the forward family is jit-compiled through the shared ``jitted``
    compile cache (DESIGN.md §7): compilations are keyed by (function
    key, input shape) — NEVER by partition point or probe layer — and
    counted by ``trace_count``, which tests assert is O(1) in depth.
"""
from __future__ import annotations

import abc
import collections
import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.core import noise as noise_lib
from repro.core.cost_model import LayerSpec
from repro.core.partition import DeviceSegment, segment_memory_bytes
from repro.core.solver import PartitionPlan

_EVAL_MEMO_SLOTS = 4         # distinct test sets remembered per backend


class ModelBackend(abc.ABC):
    """Architecture adapter for the QPART serving pipeline."""

    cfg: object          # the family's config dataclass
    params: object       # canonical full-precision parameters

    # -- shared compile cache -------------------------------------------
    # Backends are dataclasses; caches live in __dict__ lazily so
    # subclasses don't have to declare (or hash/compare) them.
    def jitted(self, key, make_fn, **jit_kw):
        """The compiled executable for ``key`` — building and jitting
        ``make_fn()`` on first use. ``jax.jit`` keys recompilation by
        input shape under the hood, so a cache entry is really a family
        of executables keyed (key, input shape): deployments that share
        ``(p, input shape)`` share one compiled program across requests.

        The program is named after the key (its first element for a
        tuple key): ``"embed"`` lowers to the module ``jit_embed``, so a
        profiler trace names the program each device run belongs to.
        Each trace (the python body runs only when XLA traces) bumps
        ``counters["trace.<name>"]``; ``trace_count`` is their sum."""
        cache = self.__dict__.setdefault("_jit_cache", {})
        if key not in cache:
            fn = make_fn()
            name = key if isinstance(key, str) else key[0]
            counter = "trace." + name
            counters = self.counters

            def counted(*a, _fn=fn, **k):
                counters[counter] += 1
                return _fn(*a, **k)

            counted.__name__ = counted.__qualname__ = name
            cache[key] = jax.jit(counted, **jit_kw)
        return cache[key]

    @property
    def counters(self) -> collections.Counter:
        """Event counts of the backend's caches, by name: ``trace.<program>``
        (XLA traces of one jitted program), where a backend caches
        quantized trees ``stack.hit``/``stack.miss``/``stack.evict``, and
        the decode sessions' ``segment.skip`` (server segment calls not
        dispatched because [p, L) is empty)."""
        return self.__dict__.setdefault("_counters", collections.Counter())

    @property
    def trace_count(self) -> int:
        """XLA trace (compilation) count across the backend's jitted
        forward family — O(1) in depth for compile-once backends."""
        return sum(v for k, v in self.counters.items()
                   if k.startswith("trace."))

    # -- structure ------------------------------------------------------
    @property
    @abc.abstractmethod
    def num_layers(self) -> int:
        """Number of partitionable layers L."""

    @abc.abstractmethod
    def layer_specs(self, batch: int = 1,
                    seq_len: Optional[int] = None) -> List[LayerSpec]:
        """(z_w, z_x, o, byte columns) per partitionable layer for a
        request shape. Implementations pass their analytic builder's
        output through ``refine_specs`` so measured per-layer overrides
        (``set_layer_cost_overrides``) apply uniformly."""

    def set_layer_cost_overrides(self, per_layer,
                                 batch: int = 1) -> None:
        """Install measured per-layer cost columns (CostModel v2): a
        list of ``{"o": MACs, "act_bytes": B, "w_bytes16": B}`` dicts —
        e.g. from ``roofline.analysis.layer_costs_from_hlo`` on the
        compiled forward — normalized here by ``batch`` (the shape they
        were measured at) and re-scaled per request batch in
        ``refine_specs``. ``None`` entries / missing keys keep the
        analytic value. Pass ``per_layer=None`` to clear."""
        if per_layer is None:
            self.__dict__.pop("_spec_overrides", None)
            return
        if len(per_layer) != self.num_layers:
            raise ValueError(
                f"need {self.num_layers} per-layer overrides, "
                f"got {len(per_layer)}")
        norm = []
        for ov in per_layer:
            ov = dict(ov or {})
            for k in ("o", "act_bytes"):        # batch-scaled columns
                if k in ov:
                    ov[k] = float(ov[k]) / batch
            norm.append(ov)
        self.__dict__["_spec_overrides"] = norm

    def refine_specs(self, specs: List[LayerSpec],
                     batch: int = 1) -> List[LayerSpec]:
        """Apply installed per-layer cost overrides to an analytic spec
        list (identity when none are installed)."""
        overrides = self.__dict__.get("_spec_overrides")
        if overrides is None:
            return specs
        out = []
        for sp, ov in zip(specs, overrides):
            kw = {}
            if "o" in ov:
                kw["o"] = ov["o"] * batch
            if "act_bytes" in ov:
                kw["act_bytes"] = ov["act_bytes"] * batch
            if "w_bytes16" in ov:
                kw["w_bytes16"] = float(ov["w_bytes16"])
            out.append(dataclasses.replace(sp, **kw) if kw else sp)
        return out

    @abc.abstractmethod
    def input_elements(self) -> float:
        """Elements of one raw input example — what a full offload (p=0)
        uploads at 32 bits (the plan table's ``input_z``)."""

    # -- forward family (calibration + measurement) ---------------------
    @abc.abstractmethod
    def forward(self, x, params=None):
        """Full forward: input batch -> logits (B, C)."""

    @abc.abstractmethod
    def forward_from_layer(self, a, start: int, params=None):
        """Resume from the activation ENTERING layer ``start`` (0-based):
        the server-side tail after a partition at p = start."""

    @abc.abstractmethod
    def layer_activations(self, x, params=None):
        """(activations entering each layer [x_1..x_L], logits)."""

    @abc.abstractmethod
    def with_layer_quantized(self, layer: int, bits: int):
        """Params tree with layer ``layer``'s weights fake-quantized at
        ``bits`` — the Alg. 1 noise probe's perturbed model."""

    # -- autoregressive decode (optional capability) --------------------
    # Token-by-token serving (DESIGN.md §11). Backends without a decode
    # path (classifiers) keep the defaults: ``supports_decode`` False,
    # ``kv_bytes_row`` None (no cache feasibility term is priced in).
    supports_decode: bool = False

    def decode_layer_specs(self, batch: int = 1,
                           context_len: Optional[int] = None) -> List[LayerSpec]:
        """Per-layer specs of ONE decode step against a ``context_len``
        context — the per-token pricing terms (MACs, cache read/write
        bytes, per-token cut payload)."""
        raise NotImplementedError(
            f"{type(self).__name__} has no autoregressive decode path")

    def kv_bytes_row(self, batch: int = 1):
        """(P+1,) cumulative device-resident decode-cache footprint per
        candidate cut, or ``None`` when no cache feasibility term
        applies (non-decode backends, or decode_max_len unset). Priced
        into the ``DeviceProfile.memory_bytes`` mask by ``price_window``
        and ``QPARTServer.serve``."""
        return None

    # -- calibration probes (Alg. 1 steps 7-9) --------------------------
    def calibrate_probes(self, x, probe_bits: int = noise_lib.PROBE_BITS):
        """Per-layer output-noise energies for the Alg. 1 calibration:
        (e_w (L,), e_x (L,), clean logits). e_w[l] is the squared logit
        perturbation from quantizing layer l's WEIGHTS at ``probe_bits``;
        e_x[l] the same for layer l's input ACTIVATION.

        Default: the scalar reference loop (``core.noise
        .backend_layer_energies`` — 1 full + 2 suffix forwards per
        layer). Compile-once backends override with a vectorized probe
        that emits all L energies from a single compiled program;
        overrides are regression-locked against this reference."""
        return noise_lib.backend_layer_energies(self, x, probe_bits)

    # -- quantized device-segment execution -----------------------------
    @abc.abstractmethod
    def split(self, plan: PartitionPlan) -> DeviceSegment:
        """The quantized device segment (layers 1..p at the plan's
        per-layer bit-widths): bits and wire size now, the quantized
        weights on the first read of its ``params``. The server side
        keeps the backend's own full-precision params."""

    @abc.abstractmethod
    def run_device_segment(self, seg: DeviceSegment, plan: PartitionPlan, x):
        """Run layers 1..p on the quantized segment and return the cut
        activation, quantized at the plan's ``bits_x`` for the uplink."""

    # -- shared logic (family-independent) ------------------------------
    def device_executor(self, plan: PartitionPlan) -> "DeviceExecutor":
        """Callable quantized device segment for ``plan``."""
        return DeviceExecutor(self, plan, self.split(plan))

    def execute_plan(self, plan: PartitionPlan, x,
                     executor: Optional["DeviceExecutor"] = None):
        """Really run the partitioned, quantized model: quantized device
        segment, quantized cut activation, full-precision server tail.
        ``executor`` reuses an already-materialized device segment
        (``Deployment`` passes its cached one)."""
        if plan.p == 0:
            return self.forward(x)
        h = (executor or self.device_executor(plan))(x)
        return self.forward_from_layer(h, plan.p)

    def evaluate(self, x, y, params=None) -> float:
        """Top-1 accuracy of the (full-precision) forward on (x, y).

        Memoized per test-set IDENTITY (the exact array objects) when run
        on the backend's own params: a window of deployments executing
        against one test set pays for the baseline forward once
        (``Deployment.execute`` calls this per deployment)."""
        if params is not None:
            return self._measure(x, y, params)
        memo = self.__dict__.setdefault("_eval_memo", [])
        for mx, my, val in memo:
            if mx is x and my is y:
                return val
        val = self._measure(x, y, self.params)
        memo.append((x, y, val))
        del memo[:-_EVAL_MEMO_SLOTS]
        return val

    def _measure(self, x, y, params) -> float:
        logits = self.forward(x, params=params)
        return float(jnp.mean(jnp.argmax(logits, -1) == y))


@dataclasses.dataclass
class DeviceExecutor:
    """A quantized device segment, callable on inputs: what a
    ``Deployment`` ships to the edge device. ``__call__`` maps a raw input
    batch to the quantized cut activation (the uplink payload). The
    compiled executable behind it comes from the backend's shared
    ``jitted`` cache, so executors for the same (p, input shape) reuse
    one compilation."""
    backend: ModelBackend
    plan: PartitionPlan
    segment: DeviceSegment

    def __call__(self, x):
        return self.backend.run_device_segment(self.segment, self.plan, x)

    @property
    def payload_bits(self) -> float:
        return self.segment.payload_bits

    @property
    def memory_bytes(self) -> float:
        return segment_memory_bytes(self.segment)
