"""The check decides ``correct``: a whole run at toy size on the CPU
(``--rehearse``: the harness's look for a chip is skipped, everything
else runs) passes as the program stands, and fails with the timed path
broken underneath: a token altered where it is produced, and a decode
step that returns its cache unchanged. The control, the float8 reference
in the program's place, reads a gap well above the program's and comes
out not correct under the cell's own limits, at four layers of the
published widths (``--rehearse wide``; at toy widths the logits are too
small for float8 to move them past the limits)."""
from __future__ import annotations

import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from bench import run  # noqa: E402

CELL = "smollm-135m.edge"


def _run(seed=5, control=False, widths="toy", seconds=3):
    args = run.parse(["--workload", CELL, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", "0", "--rehearse", widths])
    return run.run_cell(args, control=control)


@pytest.fixture(scope="module", autouse=True)
def _program_on_path():
    run.prepare_process(rehearse=True)


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0


def test_altered_token_fails(monkeypatch):
    from repro.serving.decode import pipeline
    step = pipeline.DecodeSession.step

    def altered(self, token):
        return (step(self, token) + 1) % self.cfg.vocab_size

    monkeypatch.setattr(pipeline.DecodeSession, "step", altered)
    res = _run()
    assert not res["correct"], res["checks"]


def test_unchanged_cache_fails(monkeypatch):
    from repro.serving.backends import transformer
    decode = transformer.TransformerBackend.decode_segment

    def stale(self, x, caches, pos, start, stop, params=None):
        out, _ = decode(self, x, caches, pos, start, stop, params=params)
        return out, caches

    monkeypatch.setattr(transformer.TransformerBackend, "decode_segment",
                        stale)
    res = _run()
    assert not res["correct"], res["checks"]


@pytest.fixture(scope="module")
def control_runs():
    return [_run(seed, control=True, widths="wide", seconds=12)
            for seed in (11, 12, 13)]


@pytest.mark.parametrize("number", ["logit_gap", "mean_gap"])
def test_control_reads_above_the_program(control_runs, number):
    prog = [r["checks"][number]["value"] for r in control_runs]
    ctl = [r["checks"]["control_" + number]["value"] for r in control_runs]
    assert min(ctl) >= 3 * max(prog), (prog, ctl)


def test_control_is_not_correct(control_runs):
    for r in control_runs:
        assert r["correct"], r["checks"]
        assert not r["control_correct"], r["checks"]
