"""KV-cache dtype and footprint plumbing for the partitioned decode
pipeline, and the page-granular residency the fleet prices.

The cut splits cache OWNERSHIP: the device holds the caches of its
quantized segment ``[0, p)``, the server the tail's ``[p, L)``. A side
with a non-empty segment allocates a FULL stacked
``transformer.init_cache`` tree (the compile-once segment programs scan
all layers and mask the inactive ones), but only its own segment's
slices are ever written — the rest stay zeros, a simulation artifact
whose cost is excluded from the footprint accounting below. A side with
an empty segment holds no cache: the device at ``p == 0``, the server
at ``p == L``.

A quantized device segment stores its cache at the deployed bit-width's
storage dtype instead of silently upcasting to bf16: ≤8-bit plans get
``float8_e4m3fn`` (1 B/elem — storage only; attention always computes
in the query dtype, see ``models.attention.attention_decode``), ≤16-bit
plans bf16, and full-precision plans the model dtype. SSM recurrent
state stays f32 regardless (``init_ssm_cache`` pins it) — only the conv
ring follows the storage dtype.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from repro.models.transformer import num_periods, period_len


def kv_cache_dtype(bits, model_dtype=jnp.bfloat16):
    """Storage dtype of a decode cache deployed at ``bits`` activation
    bits. ``None``/0 bits means full precision (the server tail)."""
    if not bits:
        return model_dtype
    b = int(math.ceil(float(bits)))
    if b <= 8:
        return jnp.float8_e4m3fn
    if b <= 16:
        return jnp.bfloat16
    return model_dtype


def tree_cache_bytes(caches) -> int:
    """Total allocated bytes of an ``init_cache`` tree (all layers)."""
    return int(sum(leaf.nbytes for leaf in jax.tree.leaves(caches)))


def segment_cache_bytes(cfg, caches, start: int, stop: int) -> int:
    """Bytes of the cache slices owned by segment ``[start, stop)`` of a
    stacked ``init_cache`` tree — what the segment's holder actually
    pays for (layer l lives at index ``l // plen`` of period-position
    ``l % plen``'s leaves, one of ``nper`` equal slices)."""
    plen, nper = period_len(cfg), num_periods(cfg)
    total = 0
    for layer in range(start, stop):
        pos = layer % plen
        total += sum(leaf.nbytes // nper
                     for leaf in jax.tree.leaves(caches[pos]))
    return total


def paged_kv_ctx(tokens: int, page_tokens: int, max_len: int) -> int:
    """Context length a ``tokens``-token stream is PRICED at under paged
    allocation: rounded up to the page boundary, capped by the dense
    worst case. Strictly <= ``max_len`` — the admission mask can only
    widen."""
    if page_tokens <= 0:
        return max_len
    pages = -(-int(tokens) // int(page_tokens))
    return min(pages * int(page_tokens), int(max_len))


class PageLedger:
    """Pure residency accounting for the fleet engine's decode lane,
    priced at page granularity (``paged_kv_ctx``; the fleet simulates at
    cost-model granularity; no tensors move). Tracks per-stream
    page-granular device-KV bytes, the fleet-wide current/peak, and the
    no-leak invariant: after every stream finishes or severs,
    ``resident_bytes == 0`` and ``open_streams == 0``."""

    def __init__(self):
        self._held: Dict[int, float] = {}       # stream index -> bytes
        self._pages: Dict[int, int] = {}        # stream index -> pages
        self.resident_bytes = 0.0
        self.peak_bytes = 0.0
        self.total_page_allocs = 0
        self.total_page_frees = 0

    @property
    def open_streams(self) -> int:
        return len(self._held)

    @property
    def resident_pages(self) -> int:
        return sum(self._pages.values())

    def open(self, index: int, nbytes: float, pages: int) -> None:
        self.close(index)                       # idempotent re-open
        self._held[index] = float(nbytes)
        self._pages[index] = int(pages)
        self.resident_bytes += float(nbytes)
        self.total_page_allocs += int(pages)
        self.peak_bytes = max(self.peak_bytes, self.resident_bytes)

    def grow(self, index: int, nbytes: float, pages: int) -> None:
        """Raise stream ``index``'s residency to ``nbytes``/``pages``
        (monotone: paged KV never shrinks mid-stream — ring reuse
        overwrites in place)."""
        if index not in self._held:
            return
        d_bytes = max(0.0, float(nbytes) - self._held[index])
        d_pages = max(0, int(pages) - self._pages[index])
        self._held[index] += d_bytes
        self._pages[index] += d_pages
        self.resident_bytes += d_bytes
        self.total_page_allocs += d_pages
        self.peak_bytes = max(self.peak_bytes, self.resident_bytes)

    def close(self, index: int) -> int:
        """Finish/sever: release the stream's pages. Returns the count."""
        nbytes = self._held.pop(index, 0.0)
        pages = self._pages.pop(index, 0)
        self.resident_bytes -= nbytes
        if not self._held:
            self.resident_bytes = 0.0           # clamp fp residue at empty
        self.total_page_frees += pages
        return pages
