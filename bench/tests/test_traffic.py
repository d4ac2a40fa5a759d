"""The generator gives every seed the same work in another order."""
from __future__ import annotations

import collections
import json
import pathlib

import numpy as np
import pytest

from bench.core import stats, traffic

EDGE = json.loads((pathlib.Path(__file__).parents[1] / "traffic"
                   / "edge.json").read_text())


def _work(reqs):
    return ([len(r.prompt) for r in reqs], [r.max_new_tokens for r in reqs],
            [r.context for r in reqs], [r.due_s for r in reqs])


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**40 + 3])
def test_same_schedule_every_seed(seed):
    ref = traffic.schedule(EDGE, 2.0, 45.0, 1, 1000)
    got = traffic.schedule(EDGE, 2.0, 45.0, seed, 1000)
    assert _work(ref) == _work(got)
    assert not all(np.array_equal(a.prompt, b.prompt)
                   for a, b in zip(ref, got))


def test_deterministic_and_in_window():
    a = traffic.schedule(EDGE, 3.0, 20.0, 42, 500)
    b = traffic.schedule(EDGE, 3.0, 20.0, 42, 500)
    assert len(a) == 60
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    due = [r.due_s for r in a]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 20.0
    assert all((r.prompt >= 0).all() and (r.prompt < 500).all() for r in a)


def test_shares_and_lengths():
    two = dict(EDGE, contexts=[dict(c, share=1) for c in EDGE["contexts"] * 2])
    reqs = traffic.schedule(two, 2.0, 50.0, 3, 100)
    counts = collections.Counter(len(r.prompt) for r in reqs)
    assert counts == {128: 50, 256: 30, 512: 20}
    outs = [r.max_new_tokens for r in reqs]
    assert min(outs) >= 8 and max(outs) <= 128
    assert stats.percentile(outs, 50) == pytest.approx(32, abs=1)
    assert collections.Counter(r.context for r in reqs) == {0: 50, 1: 50}


def test_percentile_and_rate():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile([1, 2, 3, 4, 5], 90) == pytest.approx(4.6)
    assert stats.rate(30, 12.0) == 2.5
