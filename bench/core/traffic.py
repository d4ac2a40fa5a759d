"""The one traffic generator: a traffic file's parameters -> a request
schedule.

Every seed gets the same schedule. For ``n`` requests the generator
takes stratified draws (the quantile at (i + 1/2) / n of each
distribution) of the inter-arrival gap, the output length, and exact
shares of the prompt-length buckets and device contexts, and shuffles
each list in one fixed order (``ORDER_SEED``). The run's seed draws the
prompt token ids (and the benchmark's weights). A window holds a dozen
to a few dozen requests, where the order of long and short requests
alone moved the median time to first token by 30% between seeds
(PERF.md); with the order fixed, seeds differ by token ids and weights
only.

Arrivals are an open loop: request i is due at ``due_s`` seconds after
the window opens, whatever the server is doing.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np

# the one order of every schedule's gaps, lengths and contexts
ORDER_SEED = 0


@dataclasses.dataclass
class Request:
    index: int
    due_s: float
    prompt: np.ndarray          # (prompt_len,) int32 token ids
    max_new_tokens: int
    context: int                # index into the traffic file's contexts


def _shares(n: int, shares) -> np.ndarray:
    """Exact counts per share summing to n (largest remainders)."""
    shares = np.asarray(shares, np.float64) / np.sum(shares)
    raw = shares * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts))[:n - counts.sum()]:
        counts[i] += 1
    return counts


def _stratified(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def output_lengths(n: int, spec: dict) -> np.ndarray:
    """Log-normal with the file's median and sigma, clipped, stratified."""
    z = np.array([NormalDist().inv_cdf(u) for u in _stratified(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)


def gaps(n: int, rate: float) -> np.ndarray:
    """Stratified exponential inter-arrival gaps at ``rate`` per second."""
    return -np.log1p(-_stratified(n)) / rate


def schedule(traffic: dict, rate: float, seconds: float, seed: int,
             vocab: int) -> list[Request]:
    """The requests due in a window of ``seconds`` at ``rate``: as many
    as the rate offers in the window, the last one due before it
    closes."""
    n = max(int(round(rate * seconds)), 1)
    order = np.random.default_rng(ORDER_SEED)
    rng = np.random.default_rng(seed)
    g = gaps(n, rate)
    # the stratified gaps sum to about n / rate; scale them so that the
    # n-th arrival falls half a mean gap before the window closes
    g = g * (seconds - 0.5 / rate) / g.sum()
    due = np.cumsum(order.permutation(g))
    buckets = np.repeat(traffic["prompt_len"]["buckets"],
                        _shares(n, traffic["prompt_len"]["shares"]))
    outs = output_lengths(n, traffic["output_len"])
    ctx_shares = [c.get("share", 1.0) for c in traffic["contexts"]]
    ctxs = np.repeat(np.arange(len(ctx_shares)), _shares(n, ctx_shares))
    buckets, outs, ctxs = (order.permutation(x)
                           for x in (buckets, outs, ctxs))
    return [Request(i, float(due[i]),
                    rng.integers(0, vocab, int(buckets[i]), dtype=np.int32),
                    int(outs[i]), int(ctxs[i]))
            for i in range(n)]
