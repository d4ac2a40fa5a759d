"""Model-segment splitting: materialize the (quantized) device segment and
the server segment at a partition point.

Two views of the same abstraction (DESIGN.md §3):
  * edge view  — classifier params split into python lists; the device list
                 is fake-quantized at the plan's per-layer bit-widths;
  * pod view   — a mesh-sharding split for transformers where the "device"
                 maps to a mesh slice (used by the serving engine).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, List, MutableMapping, Sequence

import numpy as np

from repro.core.quantizer import fake_quant, payload_bits, round_bits
from repro.core.solver import PartitionPlan


@dataclasses.dataclass
class DeviceSegment:
    """The quantized device segment (layers 1..p). The bits, the wire
    size and the per-layer element counts are host bookkeeping, set at
    split; the quantized weights are built by ``build`` on the first read
    of ``params`` and kept."""
    bits_w: np.ndarray
    bits_x: int
    payload_bits: float          # exact wire size (Eq. 14)
    layer_elements: List[int]    # parameter elements of each device layer
    build: Callable[[], list] = dataclasses.field(repr=False)

    @functools.cached_property
    def params(self) -> list:
        """Quantized layer params (layers 1..p), built on first read."""
        return self.build()


def num_elements(tree, lead_axes: int = 0) -> int:
    """Elements of a pytree's leaves, less ``lead_axes`` leading axes of
    each (1 for a stacked period axis). Read from shapes only."""
    import jax
    return sum(int(np.prod(v.shape[lead_axes:]))
               for v in jax.tree.leaves(tree))


def split_blocks(layer_at: Callable[[int], Any], layer_elements: Sequence[int],
                 plan: PartitionPlan, layer_specs,
                 counters: MutableMapping[str, int]) -> DeviceSegment:
    """Split + quantize a model at plan.p. ``layer_at(i)`` is layer i's
    full-precision params (classifier layer dicts, transformer block
    pytrees — any pytree per layer) and ``layer_elements[i]`` its element
    count. The bits and the wire size are computed here, on the host;
    ``layer_at`` and ``fake_quant`` run only when the segment's
    ``params`` are first read, which bumps ``counters
    ["split.materialize"]``. The server side keeps the caller's
    full-precision params."""
    import jax
    p = plan.p
    bits_int = round_bits(plan.bits_w) if p else np.zeros(0, int)
    elements = [int(n) for n in layer_elements[:p]]
    wire = 0.0
    for n, b in zip(elements, bits_int):
        wire += float(payload_bits(n, int(b)))
    bits_x = int(round_bits(plan.bits_x)) if p else 32
    # activation payload counted when the device sends the cut activation
    wire_x = float(payload_bits(int(layer_specs[p - 1].z_x), bits_x)) if p else 0.0

    def build() -> list:
        counters["split.materialize"] += 1
        return [jax.tree.map(lambda t, b=int(b): fake_quant(t, b),
                             layer_at(i))
                for i, b in enumerate(bits_int)]

    return DeviceSegment(bits_int, bits_x, wire + wire_x, elements, build)


def split_classifier(params: List[dict], plan: PartitionPlan, layer_specs,
                     counters: MutableMapping[str, int],
                     ) -> tuple[DeviceSegment, List[dict]]:
    """Split + quantize a classifier at plan.p. Returns (device, server)."""
    seg = split_blocks(params.__getitem__, [num_elements(lp) for lp in params],
                       plan, layer_specs, counters)
    return seg, list(params[plan.p:])


def segment_memory_bytes(seg: DeviceSegment) -> float:
    """Device memory footprint of the quantized segment (packed codes)."""
    total = 0.0
    for n, b in zip(seg.layer_elements, seg.bits_w):
        total += n * int(b) / 8.0
    return total


def plan_memory_bytes(plan: PartitionPlan, layer_specs) -> float:
    """Analytic device memory (bytes) a plan's quantized segment occupies
    at the deployed (ceil-rounded) bit-widths — the quantity serve-time
    admission checks against ``DeviceProfile.memory_bytes``. Equals
    ``plan.device_memory_bytes`` when the plan came out of the solver;
    provided for plans built elsewhere (baseline stubs, tests)."""
    if plan.p == 0:
        return 0.0
    bits = np.clip(np.ceil(np.asarray(plan.bits_w, np.float64)), 2, 16)
    z_w = np.array([sp.z_w for sp in layer_specs[:plan.p]], np.float64)
    return float(np.sum(bits * z_w) / 8.0)
