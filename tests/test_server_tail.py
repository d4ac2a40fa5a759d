"""The server tail at p = L (``DecodeSession``): the empty server segment
[L, L) dispatches no program and holds no cache, and the unembed runs
final norm + head without a block scan. Every logit is bitwise what the
masked path gives (the segment program over [L, L), then ``h_logits``
over [L, L)), the routed layer's counters are those the masked calls
would have left, and ``segment.skip`` counts each call not dispatched.
Plans with a server segment (p = 0, p = L // 2) still run it, count no
skip, and keep the p = 0 stream. For the reduced dense SmolLM and
Moonlight's toy stand-in (``bench/families/mla_moe.py``)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.solver import PartitionPlan
from repro.models import transformer as T
from repro.models.moe import MOE_STATS
from repro.serving.backends import TransformerBackend
from repro.serving.decode import DecodeSession

PROMPT, STEPS, MAX_LEN = 12, 6, 32


def _plan(p: int, bits: float) -> PartitionPlan:
    return PartitionPlan(p=p, bits_w=np.full(p, float(bits)),
                         bits_x=float(bits), objective=0.0, psi_total=0.0,
                         payload_bits=0.0, breakdown={})


def _smollm():
    cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                              dtype="float32")
    return TransformerBackend(cfg, T.init_params(jax.random.key(0), cfg),
                              seq_len=PROMPT, decode_max_len=MAX_LEN)


def _moonlight():
    from bench.core import program, spec
    from bench.core.weights import make_program_params
    family = spec.family_module("mla_moe")
    m = family.rehearsal(spec.load_cell("moonlight-16b-a3b.edge").model,
                         "toy")
    cfg = dataclasses.replace(program.program_config(family, m),
                              dtype="float32")
    return TransformerBackend(cfg, make_program_params(2 ** 33 + 17, m, cfg,
                                                       family),
                              seq_len=PROMPT, decode_max_len=MAX_LEN)


@pytest.fixture(scope="module", params=["smollm", "moonlight"])
def backend(request):
    return {"smollm": _smollm, "moonlight": _moonlight}[request.param]()


def _prompt(vocab):
    return ((np.arange(PROMPT, dtype=np.int32) * 37 + 11) % vocab)[None]


def _masked_tail(b: TransformerBackend, h, caches, pos, stats, prefill):
    """The server tail at [L, L) as it ran before the skip: the segment
    program over the empty segment, then ``h_logits`` over it too.
    -> (logits, caches, stats)."""
    L = b.num_layers
    call = b.extend_segment if prefill else b.decode_segment
    kw = {} if stats is None else {"stats": stats}
    out = call(h, caches, jnp.asarray(pos, jnp.int32), L, L, **kw)
    h, caches = out[:2]
    if stats is not None:
        stats = out[2]
    return b._h_logits()(b.params, h[:, -1:, :], L, L), caches, stats


def _stream(sess: DecodeSession, prompt, steps: int):
    """[(logits, token)] of the prefill and ``steps`` decode steps."""
    tok = sess.prefill(prompt)
    out = [(np.asarray(sess.logits), np.asarray(tok))]
    for _ in range(steps):
        tok = sess.step(tok)
        out.append((np.asarray(sess.logits), np.asarray(tok)))
    return out


def test_p_eq_L_is_bitwise_the_masked_path(backend):
    """At p = L the session's logits, tokens and routed-layer counters
    equal the masked path's, fed the same hop outputs; the server holds
    no cache and ``segment.skip`` counts the prefill and each step."""
    cfg, L = backend.cfg, backend.num_layers
    sess = DecodeSession(backend, _plan(L, 8), max_len=MAX_LEN,
                         qkernels=cfg.mla is not None)
    assert sess._cache_extendable
    hops = []
    quant_hop = sess._quant_hop
    sess._quant_hop = lambda h: hops.append(quant_hop(h)) or hops[-1]
    skips = backend.counters["segment.skip"]
    got = _stream(sess, _prompt(cfg.vocab_size), STEPS)

    assert sess.srv_caches is None and sess.server_cache_bytes() == 0
    assert backend.counters["segment.skip"] - skips == 1 + STEPS
    caches = T.init_cache(cfg, 1, MAX_LEN, sess.model_dtype)
    stats = (jnp.zeros((L, len(MOE_STATS)), jnp.int32)
             if sess.moe_stats is not None else None)
    assert len(hops) == 1 + STEPS
    for i, (h, (logits, tok)) in enumerate(zip(hops, got)):
        pos = PROMPT + i - 1 if i else 0
        want, caches, stats = _masked_tail(backend, h, caches, pos, stats,
                                           prefill=i == 0)
        np.testing.assert_array_equal(logits, np.asarray(want))
        np.testing.assert_array_equal(
            tok, np.asarray(jnp.argmax(want, -1).astype(jnp.int32)))
    if stats is not None:
        # the skipped calls would have added nothing to moe.*
        assert not np.asarray(stats).any()


@pytest.mark.parametrize("cut", ["p0", "half"])
def test_server_segment_still_runs_below_L(backend, cut):
    """Plans with a server segment dispatch it, count no skip, and keep
    the p = 0 stream of the masked unembed: bitwise at p = 0, where only
    the unembed program changed, and in its tokens at p = L // 2 with a
    lossless device segment and hop."""
    cfg, L = backend.cfg, backend.num_layers
    prompt = _prompt(cfg.vocab_size)
    caches = T.init_cache(cfg, 1, MAX_LEN, getattr(jnp, cfg.dtype))
    stats = (jnp.zeros((L, len(MOE_STATS)), jnp.int32)
             if T.moe_stats_enabled(cfg) else None)
    kw = {} if stats is None else {"stats": stats}
    h, caches = backend.extend_segment(backend.embed(prompt), caches,
                                       jnp.int32(0), 0, L, **kw)[:2]
    want = [np.asarray(backend._h_logits()(backend.params, h[:, -1:, :],
                                           L, L))]
    for i in range(STEPS):
        tok = jnp.argmax(want[-1], -1).astype(jnp.int32).reshape(-1, 1)
        x, caches = backend.decode_segment(
            backend.embed(tok), caches, jnp.int32(PROMPT + i), 0, L,
            **kw)[:2]
        want.append(np.asarray(backend._h_logits()(backend.params, x, L,
                                                   L)))

    p = 0 if cut == "p0" else L // 2
    skips = backend.counters["segment.skip"]
    sess = DecodeSession(backend, _plan(p, 32), max_len=MAX_LEN)
    got = _stream(sess, prompt, STEPS)
    assert backend.counters["segment.skip"] == skips
    assert sess.srv_caches is not None and sess.server_cache_bytes() > 0
    for (logits, tok), w in zip(got, want):
        np.testing.assert_array_equal(tok, np.argmax(w, -1))
        if p == 0:
            np.testing.assert_array_equal(logits, w)
