"""How ``correct`` is decided for a served model.

After the window has closed and the program's state is freed, a sample
drawn from the seed of the requests the window finished (always with the
one that served the most tokens in it, and at least ``MIN_TOKENS``
served tokens in all) is run through the plain reference of the model's
family (``bench/families/<family>.py``: ``Reference``), teacher-forced
on each prompt followed by its served tokens. The number compared is
the widest gap, over every sampled served token, by which the served
token's reference logit lies below the reference's best logit at that
position (greedy decoding serves the best token, so a sound run reads
rounding and quantization-boundary noise only).

``control`` reads the same gap for the tokens the float8 control puts
first at the same positions: the check has to fail it.
"""
from __future__ import annotations

import time

import numpy as np

MIN_TOKENS = 256


def sample(records, seed: int) -> list:
    done = [r for r in records if r.done]
    if not done:
        return []
    rng = np.random.default_rng([seed, 3])
    longest = max(done, key=lambda r: len(r.tokens))
    rest = [r for r in done if r is not longest]
    out, n = [longest], len(longest.tokens)
    for i in rng.permutation(len(rest)):
        if n >= MIN_TOKENS:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def check(family, model: dict, seed: int, records, prompts: dict,
          traffic: dict, control: bool = False,
          log=lambda msg: None) -> dict:
    """-> {"logit_gap": widest served gap, "tokens": compared,
    "requests": sampled[, "control_gap": ...]}."""
    picked = sample(records, seed)
    if not picked:
        return {"logit_gap": float("inf"), "mean_gap": float("inf"),
                "off_share": 1.0, "tokens": 0, "requests": 0}
    seq_pad = max(traffic["prompt_len"]["buckets"]) \
        + traffic["output_len"]["max"]
    t = time.perf_counter()
    ref = family.Reference(model, seed, seq_pad,
                           traffic["output_len"]["max"])
    log(f"[check] reference weights {time.perf_counter() - t:.3f} s")
    vocab = ref.n["V"]
    gaps, ctls = [], []
    for r in picked:
        served = np.asarray(r.tokens, np.int64)
        if np.any((served < 0) | (served >= vocab)):
            return {"logit_gap": float("inf"), "mean_gap": float("inf"),
                    "off_share": 1.0, "tokens": len(served),
                    "requests": len(picked)}
        seq = np.concatenate([prompts[r.index], served[:-1]])
        rows = np.arange(r.prompt_len - 1, r.prompt_len - 1 + len(served))
        pl = r.plan
        t = time.perf_counter()
        logits = ref.logits(seq, rows, pl.p, pl.bits_w, pl.bits_x)
        gaps.append(token_gaps(logits, served))
        if control:
            c = ref.logits(seq, rows, pl.p, pl.bits_w, pl.bits_x,
                           compute="fp8")
            ctls.append(token_gaps(logits, c.argmax(-1)))
        log(f"[check] request {r.index}: {len(served)} tokens, widest gap "
            f"{gaps[-1].max():.6f}, {time.perf_counter() - t:.3f} s")
    out = {"tokens": sum(len(g) for g in gaps), "requests": len(picked),
           **summarize(np.concatenate(gaps))}
    if control:
        out.update({f"control_{k}": v for k, v in
                    summarize(np.concatenate(ctls)).items()})
    return out


def token_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per position: how far the token's reference logit lies below the
    reference's best."""
    return ref_logits.max(-1) - ref_logits[np.arange(len(tokens)), tokens]


def summarize(gaps: np.ndarray) -> dict:
    """The widest gap, the mean gap, and the share of tokens that are not
    the reference's first choice."""
    return {"logit_gap": float(gaps.max()), "mean_gap": float(gaps.mean()),
            "off_share": float(np.mean(gaps > 0))}
