"""Moonlight-16B-A3B (the DeepSeek-V3 block) on the served path against
the plain reference of ``bench/families/mla_moe.py``, at the family's toy
sizes on the CPU: a dense layer and three expert layers, hidden 64, 4
heads, kv_lora 32, nope 16, rope 8, v 16, 4 of 8 routed experts held
(ids 2-5), top-2, one shared expert, vocabulary 512.

The program runs here in float32 (the configuration serves bfloat16 on
the chip, where ``correct`` compares them) so that the comparison sees
the mathematics: latent attention through the cache, the sigmoid-plus-
bias routing, the held share of the experts, the quantized device
segment and hop. Also: the share of the experts each chip computes sums
to the uncut layer, the routed layer drops no token, the interleaved
RoPE is the written-out formula, the grouped kernel is its oracle, the
planner's counts, and the serving knobs MLA does not take.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.core import spec
from bench.core.weights import make_program_params
from repro.configs.base import get_config
from repro.core.cost_model import transformer_layer_specs
from repro.core.solver import PartitionPlan
from repro.kernels import ref as kref
from repro.kernels.moe_gmm import gmm_pallas
from repro.models import moe as moe_lib
from repro.models.mlp import mlp_apply
from repro.models import rope as rope_lib
from repro.models import transformer as T
from repro.serving.backends import TransformerBackend
from repro.serving.decode import DecodeSession
from repro.serving.errors import ServingError

SEED = 2 ** 33 + 77
PROMPT, NEW, MAX_LEN = 12, 6, 32


@pytest.fixture(scope="module")
def family():
    return spec.family_module("mla_moe")


@pytest.fixture(scope="module")
def toy(family):
    cell = spec.load_cell("moonlight-16b-a3b.edge")
    m = family.rehearsal(cell.model, "toy")
    from bench.core import program
    cfg = dataclasses.replace(program.program_config(family, m),
                              dtype="float32")
    params = make_program_params(SEED, m, cfg, family)
    return m, cfg, params


@pytest.fixture(scope="module")
def backend(toy):
    _, cfg, params = toy
    return TransformerBackend(cfg, params, seq_len=PROMPT,
                              decode_max_len=MAX_LEN)


@pytest.fixture(scope="module")
def reference(toy, family):
    return family.Reference(toy[0], SEED, 32, 8)


def _plan(p, bits, bits_x=8.0):
    return PartitionPlan(p=p, bits_w=np.full(p, float(bits)),
                         bits_x=float(bits_x), objective=0.0, psi_total=0.0,
                         payload_bits=0.0, breakdown={})


def _prompt(vocab):
    return (np.arange(PROMPT, dtype=np.int32) * 37 + 11) % vocab


@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
@pytest.mark.parametrize("cut", ["p0", "p2", "pL"])
def test_prefill_then_decode_matches_reference(toy, backend, reference,
                                              cut, bits):
    """Every next-token logit the session computes (the prefill's, then
    each decode step's through the latent cache) against the reference's
    full forward pass over the served sequence, at the plan's cut and
    bits, the device segment's expert stacks as wire structs through the
    grouped expert kernel's oracle.

    Tolerance 1e-4 on logits of magnitude ~0.5: both sides compute in
    float32 and differ only in the order of rounding (absorbed against
    expanded attention, the grouped dispatch's sums), which reads 3e-7
    to 6e-7 here; the margin covers a float8 cache entry or a hop code
    on a rounding boundary landing on its neighbour. A missing term (a
    routed expert, the shared expert, the expert bias, the rope pairing,
    the cache) moves logits by 1e-2 or more."""
    _, cfg, _ = toy
    L = cfg.num_layers
    p = {"p0": 0, "p2": 2, "pL": L}[cut]
    plan = _plan(p, bits)
    sess = DecodeSession(backend, plan, max_len=MAX_LEN, qkernels=p > 0)
    if p:                               # layer p - 1: an expert layer
        w = sess.dev_params["blocks"][p - 1]["moe"]["w_up"]
        assert ("codes_packed" in w) == (bits <= 4)
    prompt = _prompt(cfg.vocab_size)
    tok = sess.prefill(prompt[None])
    logits, toks = [np.asarray(sess.logits[0])], [int(tok[0])]
    for _ in range(NEW - 1):
        tok = sess.step(tok)
        logits.append(np.asarray(sess.logits[0]))
        toks.append(int(tok[0]))
    seq = np.concatenate([prompt, toks[:-1]])
    rows = np.arange(PROMPT - 1, PROMPT - 1 + NEW)
    want = reference.logits(seq, rows, p, [bits] * L,
                            int(sess.bits_x) or 0)
    got = np.stack(logits)
    assert np.abs(got - want).max() < 1e-4, np.abs(got - want).max()


def test_shares_sum_to_the_uncut_layer(toy, family):
    """The routed parts every chip's share computes (two chips of 4
    experts), plus the shared expert counted once, equal the reference's
    layer holding all 8 experts. Tolerance 1e-5: float32 sums in a
    different order."""
    m, cfg, _ = toy
    n = family.dims(m)
    m_all = dict(m, n_routed_experts=8,
                 deployment=dict(m["deployment"], first_expert_held=0))
    w = family.published(jax.random.key(3), m_all)
    x = jax.random.normal(jax.random.key(4), (1, 10, n["D"]), jnp.float32)
    i = 0                                          # first expert layer
    lw = {k: w[k][i] for k in family.MOE_KEYS}
    lw["norm2"] = w["norm2"][n["dense"] + i]
    uncut = jax.jit(lambda x, lw: family._moe_ffn(
        x, lw, jnp.asarray(False), jnp.float32(16), n=family.dims(m_all),
        compute="f32") - x)
    h = T._norm(cfg, {"scale": lw["norm2"]}, x)
    shared_w = {"w_gate": lw["s_gate"], "w_up": lw["s_up"],
                "w_down": lw["s_down"]}

    @jax.jit
    def routed(h, stacks):
        parts = []
        for chip in range(2):
            c = dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe, first_held=4 * chip))
            prm = {"w_router": lw["w_router"], "e_bias": lw["e_bias"],
                   "shared": shared_w,
                   **{k: v[4 * chip:4 * chip + 4] for k, v in stacks.items()}}
            parts.append(moe_lib.moe_routed_apply(prm, c, h)[0]
                         - mlp_apply(shared_w, c, h))
        return sum(parts) + mlp_apply(shared_w, cfg, h)

    with jax.default_matmul_precision("highest"):
        want = uncut(x[0], lw)
        got = routed(h, {k: lw[k] for k in ("w_gate", "w_up", "w_down")})
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want),
                               atol=1e-5)


def _one_expert_layer(cfg, key, held_struct: bool):
    """A routed layer whose expert bias sends every token to experts 2
    and 3 (both held), as dense weights or int8 wire structs."""
    k = jax.random.split(key, 5)
    d, f, e = cfg.d_model, cfg.moe.d_ff, cfg.moe.held
    bias = jnp.zeros((cfg.moe.num_experts,)).at[2:4].set(100.0)
    prm = {"w_router": 0.1 * jax.random.normal(k[0], (d, 8)),
           "e_bias": bias,
           "w_gate": jax.random.normal(k[1], (e, d, f)) / 8,
           "w_up": jax.random.normal(k[2], (e, d, f)) / 8,
           "w_down": jax.random.normal(k[3], (e, f, d)) / 6}
    if held_struct:
        from repro.core.quantizer import quantize_stacked
        for name in ("w_gate", "w_up", "w_down"):
            prm[name] = jax.tree.map(lambda t: t[0], quantize_stacked(
                prm[name][None], 8, per_channel=False))
    return prm


@pytest.mark.parametrize("tokens", [16, 40])
@pytest.mark.parametrize("held_struct", [False, True],
                         ids=["dense", "grouped"])
def test_routed_layer_drops_no_token(toy, tokens, held_struct):
    """Every token routed to the same two experts: the rows computed all
    at once equal each token computed alone (a capacity layer would drop
    all but its capacity). 16 tokens tile by 8 rows, 40 by 128."""
    _, cfg, _ = toy
    prm = _one_expert_layer(cfg, jax.random.key(5), held_struct)
    x = jax.random.normal(jax.random.key(6), (1, tokens, cfg.d_model))
    layer = jax.jit(lambda x: moe_lib.moe_routed_apply(prm, cfg, x))
    with jax.default_matmul_precision("highest"):
        together, stats = layer(x)
        alone = [layer(x[:, i:i + 1])[0] for i in range(tokens)]
    np.testing.assert_allclose(np.asarray(together),
                               np.asarray(jnp.concatenate(alone, 1)),
                               atol=1e-5, rtol=1e-5)
    assert np.asarray(stats).tolist() == [2 * tokens, 2 * tokens, 2]


def test_interleaved_rope_is_the_written_out_formula():
    """Pair (x[2i], x[2i+1]) at position t rotates by t * theta **
    (-2i / d), written out element by element."""
    theta, d = 50_000.0, 8
    x = np.asarray(jax.random.normal(jax.random.key(7), (1, 5, 3, d)))
    pos = np.arange(5, dtype=np.int32)[None] + 3
    got = np.asarray(rope_lib.apply_rope("rope_interleaved",
                                         jnp.asarray(x), jnp.asarray(pos),
                                         theta))
    want = np.empty_like(x)
    for t in range(5):
        for i in range(d // 2):
            a = pos[0, t] * theta ** (-2.0 * i / d)
            c, s = math.cos(a), math.sin(a)
            x0, x1 = x[0, t, :, 2 * i], x[0, t, :, 2 * i + 1]
            want[0, t, :, 2 * i] = x0 * c - x1 * s
            want[0, t, :, 2 * i + 1] = x0 * s + x1 * c
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("packed", [False, True], ids=["int8", "int4"])
@pytest.mark.parametrize("live", [0, 3, 5])
def test_grouped_kernel_matches_its_oracle(packed, live):
    """``moe_gmm`` in interpret mode against ``ref.gmm_ref`` on the rows
    of live tiles (rows of later tiles are not written)."""
    e, k, n, bm, g = 4, 256, 128, 8, 5
    key = jax.random.split(jax.random.key(8), 3)
    codes = jax.random.randint(key[0], (e, k, n), 0, 16 if packed else 256)
    if packed:
        codes = kref.pack_int4_ref(codes, axis=1)
    codes = codes.astype(jnp.uint8)
    x = jax.random.normal(key[1], (g * bm, k))
    te = jnp.asarray([2, 0, 3, 3, 1], jnp.int32)
    nt = jnp.asarray(live, jnp.int32)
    scale, mu = jnp.float32(0.01), jnp.float32(-0.5)
    got = gmm_pallas(x, codes, scale, mu, te, nt, bm=bm, packed=packed,
                     bk=k // 4 if packed else k // 2, bn=n, interpret=True)
    want = kref.gmm_ref(x, codes, scale, mu, te, nt, bm=bm, packed=packed)
    rows = live * bm
    np.testing.assert_allclose(np.asarray(got)[:rows],
                               np.asarray(want)[:rows], rtol=1e-5, atol=1e-4)


# -- planner counts --------------------------------------------------------

def _counts(D, H, C, dn, dr, dv, F, Fe, E, held, k, shared, V, Ld, Lm):
    mla = D * H * (dn + dr) + D * (C + dr) + C + C * H * (dn + dv) \
        + H * dv * D + D
    dense = 3 * D * F + D
    moe_all = held * 3 * D * Fe + shared * 3 * D * Fe + D * E + E + D
    moe_act = k * held / E * 3 * D * Fe + shared * 3 * D * Fe + D * E + E \
        + D
    head = 2 * V * D + D
    return (head + Ld * (mla + dense) + Lm * (mla + moe_all),
            head + Ld * (mla + dense) + Lm * (mla + moe_act))


@pytest.mark.parametrize("shape", ["toy", "published", "cut"])
def test_param_counts(toy, shape):
    """Parameters and active parameters per token: MLA's projections and
    latent norm, the held experts' payload, k * held / E experts active
    plus the shared ones and the router (with its bias), layer 0 dense."""
    if shape == "toy":
        cfg = toy[1]
        want = _counts(64, 4, 32, 16, 8, 16, 128, 32, 8, 4, 2, 1, 512, 1, 3)
    else:
        cfg = get_config("moonlight-16b-a3b")
        if shape == "cut":
            cfg = dataclasses.replace(cfg, num_layers=5, vocab_size=20_480,
                                      moe=dataclasses.replace(
                                          cfg.moe, experts_held=8))
        held, V, Lm = (8, 20_480, 4) if shape == "cut" \
            else (64, 163_840, 26)
        want = _counts(2048, 16, 512, 128, 64, 128, 11_264, 1408, 64, held,
                       6, 2, V, 1, Lm)
    assert cfg.param_count() == want[0]
    assert cfg.active_param_count() == round(want[1])
    if shape == "published":          # the published 16B-A3B
        assert 15.9e9 < cfg.param_count() < 16.1e9
        assert 2.9e9 < cfg.active_param_count() < 3.1e9


def test_layer_specs_price_the_latent_cache_and_the_held_share(toy):
    """Decode specs: the latent cache of 576 elements a token and layer,
    the held experts' payload, and k * held / E routed experts of work."""
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"), num_layers=5,
                              vocab_size=20_480, moe=dataclasses.replace(
                                  get_config("moonlight-16b-a3b").moe,
                                  experts_held=8))
    ctx = 1000
    specs = transformer_layer_specs(cfg, ctx, batch=1, mode="decode")[1:]
    D, H, C, dr, dn, dv, Fe, F = 2048, 16, 512, 64, 128, 128, 1408, 11_264
    assert [sp.kv_bytes16 for sp in specs] == [2.0 * 576 * ctx] * 5
    mla_ops = D * H * (dn + dr) + D * (C + dr) + H * dn * C + H * C * dv \
        + H * dv * D + H * (576 + C) * ctx
    assert specs[0].o == pytest.approx(mla_ops + 3 * D * F)
    moe_ops = D * 64 + (6 * 8 / 64 + 2) * 3 * D * Fe
    assert specs[1].o == pytest.approx(mla_ops + moe_ops)
    assert specs[1].z_w == cfg._block_params(1)
    assert cfg._block_params(1) - cfg._block_params(2) == 0
    per_expert = 3 * D * Fe
    heldless = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, experts_held=4))
    assert cfg._block_params(1) - heldless._block_params(1) == \
        4 * per_expert


def test_other_moe_configs_keep_their_counts():
    """``experts_held`` defaults to every expert: the capacity layer's
    configs count as before (top_k of num_experts active)."""
    cfg = get_config("olmoe-1b-7b")
    m = cfg.moe
    assert m.held == m.num_experts == 64
    d = cfg.d_model
    attn = 4 * d * d + d
    moe_all = 64 * 3 * d * m.d_ff + d * 64 + d
    moe_act = 8 * 3 * d * m.d_ff + d * 64 + d
    head = 2 * cfg.vocab_size * d + d
    assert cfg.param_count() == head + 16 * (attn + moe_all)
    assert cfg.active_param_count() == head + 16 * (attn + moe_act)


# -- serving knobs MLA does not take ---------------------------------------

@pytest.mark.parametrize("knob", ["paged", "chunked", "speculative"])
def test_mla_session_refuses_unsupported_knobs(toy, backend, knob):
    cfg = toy[1]
    kw = {"paged": {"paged": True},
          "chunked": {"prefill_chunk_tokens": 4},
          "speculative": {"draft_tokens": 2}}[knob]
    with pytest.raises(ServingError, match="latent attention"):
        DecodeSession(backend, _plan(cfg.num_layers, 8), max_len=MAX_LEN,
                      **kw)
