"""Bring-up smoke of the QPART served path on one TPU chip.

Drives SmolLM-135M at its published widths (30 layers, d_model 576, 9/3
heads, d_ff 1536, vocab 49,152; random weights from ``--seed``) through
the entry points a user calls:

  TransformerBackend -> QPARTServer.register -> calibrate -> build_store
  -> serve -> Deployment.execute (held-out batch) -> Deployment.generate

Three device/channel/objective contexts make the planner itself pick
full offload (p = 0), a mid cut, and the whole stack on the device
(p = L) with at least one layer at <= 4 bits, so the int8 and int4
dequantize-fused kernels both run. Each deployment is checked against a
reference on the chip, with the tolerances stated below; any failure
exits non-zero. The last line of stdout is the JSON result.

Labels are the full-precision model's own greedy next tokens, so a
deployment's accuracy is its top-1 agreement with the unquantized model.

  python chip_smoke.py            # needs a TPU; exits non-zero without one

Seconds printed here are bring-up wall times that include compilation
and host work; they are not benchmark metrics.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

# -- sizes -------------------------------------------------------------------
BATCH = 8            # concurrent streams in one generate call
PROMPT = 256         # prompt tokens; also the planning sequence length
MAX_LEN = 512        # decode cache slots (a multiple of the decode kernel's
#                      256-slot block)
NEW_TOKENS = 64      # generated tokens per stream
N_CALIB = 16         # calibration sequences
N_TEST = 16          # held-out sequences for Deployment.execute
LEVELS = (0.001, 0.0025, 0.005, 0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 0.8, 0.95,
          0.99)

# -- tolerances ----------------------------------------------------------------
# Max-abs logit error, as a fraction of the reference's largest |logit|.
# Both sides of each comparison compute in bf16 with f32 accumulation; bf16
# keeps 8 mantissa bits (relative rounding 2^-9), and the error compounds
# over 30 residual blocks, so agreement to a few percent of the logit scale
# is what two correct bf16 implementations of one function reach.
#  * p = 0: the served prefill (cache-mediated extend program, jnp attention
#    over the ring) against the whole-model forward (flash attention
#    kernel, no cache): same weights, different attention programs.
#  * p > 0: the dequantize-fused kernels (f32 weights dequantized in VMEM)
#    against the dense fake-quant weights (the same codes, rounded to bf16
#    before the matmul): same quantized function, different rounding.
LOGIT_TOL = 0.05
# Device-segment hidden state, kernels vs dense fake-quant weights, as a
# fraction of the dense side's largest |value|: the same bf16 argument, on
# up to 30 blocks of matmuls whose weights differ only by the bf16 rounding
# of the dense side (the kernels dequantize to f32 in VMEM).
HIDDEN_TOL = 0.05
# Greedy tokens may differ only where the reference's top-2 logit gap is
# within twice the logit tolerance: a near-tie that an in-tolerance error
# can flip. Anywhere else a different token is a wrong result.
TIE_FACTOR = 2.0


@contextlib.contextmanager
def phase(name: str, times: dict):
    t0 = time.perf_counter()
    yield
    times[name] = time.perf_counter() - t0
    print(f"[phase] {name}: {times[name]:.3f} s", flush=True)


def require_tpu():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX found platform {dev.platform!r} "
                 f"({dev.device_kind}); this smoke runs on the chip only")
    return dev


def check_lanes():
    """The chip path runs the compiled kernels: no env override or
    fallback may put the jnp reference or interpret mode there."""
    from repro.kernels import ops
    from repro.models import attention
    mode = ops.kernel_mode()
    impl = attention._attention_impl()
    assert mode == "kernel", f"kernel lane is {mode!r}, want 'kernel'"
    assert impl == "flash", f"attention impl is {impl!r}, want 'flash'"
    print(f"[lanes] kernels={mode} attention={impl}")


def routed_leaves(params):
    """(name, leaf) of every kernel-routed projection/MLP weight."""
    from repro.models import transformer as T
    for pos, bp in enumerate(params["blocks"]):
        for parent, names in T.KERNEL_ROUTED.items():
            for name in names:
                if parent in bp and name in bp[parent]:
                    yield f"blocks[{pos}].{parent}.{name}", bp[parent][name]


def wire_kind(sess, bits):
    """What the device segment's routed weights are: "none" (p = 0),
    "dense" (bits > 8: the uint8 wire cannot carry them), "int8" or
    "int4" wire structs. Where every deployed layer is <= 8 bits they
    must be wire structs: the ``qkernels`` flag alone stays on when
    ``qstacked_for`` falls back to dense weights."""
    from repro.kernels import ops
    if sess.p == 0:
        return "none"
    assert sess.qkernels, "device segment is not on the kernel path"
    if max(bits) > 8:
        return "dense"
    leaves = dict(routed_leaves(sess.dev_params))
    for name, leaf in leaves.items():
        assert ops.is_wire_struct(leaf), f"{name} is dense on the chip path"
    packed = ["codes_packed" in leaf for leaf in leaves.values()]
    return "int4" if all(packed) else "int8"


def count_kernels(backend, sess, prompt):
    """Pallas calls in the compiled decode programs the session runs."""
    import jax.numpy as jnp
    from repro.models import transformer as T
    b = prompt.shape[0]
    x = jnp.zeros((b, 1, backend.cfg.d_model), getattr(jnp, backend.cfg.dtype))
    counts = {}
    sides = [("server", backend.params, sess.model_dtype, sess.p,
              backend.num_layers)]
    if sess.p > 0:
        sides.insert(0, ("device", sess.dev_params, sess.dev_dtype, 0,
                         sess.p))
    for side, params, dtype, start, stop in sides:
        caches = T.init_cache(backend.cfg, b, sess.max_len, dtype)
        text = backend._decode_seg().lower(
            params, x, caches, jnp.int32(0), start, stop).compile().as_text()
        counts[side] = text.count("tpu_custom_call")
        assert counts[side] > 0, f"{side} decode program has no Pallas call"
    return counts


def logit_error(got, ref):
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    assert np.all(np.isfinite(got)) and np.all(np.isfinite(ref)), "NaN/Inf"
    scale = float(np.max(np.abs(ref)))
    return float(np.max(np.abs(got - ref))), scale


def tokens_agree(got_tok, ref_logits, tol_abs):
    """Fraction of equal greedy tokens; raises on a mismatch that is not
    a near-tie of the reference."""
    ref = np.asarray(ref_logits, np.float32)
    ref_tok = ref.argmax(-1)
    got_tok = np.asarray(got_tok)
    top2 = np.sort(ref, axis=-1)[..., -2:]
    gap = top2[..., 1] - top2[..., 0]
    bad = (got_tok != ref_tok) & (gap > TIE_FACTOR * tol_abs)
    assert not bad.any(), (
        f"{int(bad.sum())} greedy tokens differ outside a near-tie "
        f"(gaps {gap[bad][:4]})")
    return float(np.mean(got_tok == ref_tok))


def compare_p0(backend, dep, prompt):
    """Served prefill logits (p = 0) against the whole-model forward."""
    sess = dep.decode_session()
    sess.prefill(prompt)
    ref = backend.forward(prompt)
    err, scale = logit_error(sess.logits, ref)
    tol = LOGIT_TOL * scale
    agree = tokens_agree(np.asarray(sess.logits).argmax(-1), ref, tol)
    assert err <= tol, f"p=0 prefill logits off by {err} > {tol}"
    return {"max_abs_logit_err": err, "logit_scale": scale,
            "token_agreement": agree}


def compare_kernels(backend, dep, prompt):
    """The dequantize-fused kernels against the dense fake-quant weights
    of the same plan.

    Asserted: the device segment's hidden state out of its prefill
    (extend) and one decode step, both weight forms run by the session's
    own programs over a bf16 cache. Reported: the two whole sessions,
    teacher-forced on the kernel session's tokens. Their logits differ
    by more than the kernels do: the cut hidden state is quantized to
    ``bits_x`` for the hop and the device cache is float8, so an
    in-tolerance difference can move a value across a rounding boundary
    — one quantization step — and the difference propagates through the
    server tail."""
    import jax.numpy as jnp
    from repro.models import transformer as T
    from repro.serving.decode import DecodeSession
    seg = dep.device_segment().segment
    sk = DecodeSession(backend, dep.plan, max_len=MAX_LEN, segment=seg,
                       qkernels=True)
    sd = DecodeSession(backend, dep.plan, max_len=MAX_LEN, segment=seg,
                       qkernels=False)
    out = {}
    p, b = sk.p, prompt.shape[0]
    hs, xs = [], []
    for params in (sk.dev_params, sd.dev_params):
        caches = T.init_cache(backend.cfg, b, MAX_LEN, sk.model_dtype)
        h = backend.embed(prompt, params=params)
        h, caches = backend.extend_segment(h, caches, jnp.int32(0), 0, p,
                                           params=params)
        x = backend.embed(prompt[:, -1:], params=params)
        x, _ = backend.decode_segment(x, caches, jnp.int32(prompt.shape[1]),
                                      0, p, params=params)
        hs.append(h)
        xs.append(x)
    for key, (got, ref) in (("prefill_hidden", hs), ("decode_hidden", xs)):
        err, scale = logit_error(got, ref)
        assert err <= HIDDEN_TOL * scale, (
            f"{key}: kernel vs dense off by {err} > {HIDDEN_TOL * scale}")
        out[key] = {"max_abs_err": err, "scale": scale}

    tok = sk.prefill(prompt)
    sd.prefill(prompt)
    errs, scales, agree = [], [], []
    for i in range(NEW_TOKENS):
        err, scale = logit_error(sk.logits, sd.logits)
        errs.append(err)
        scales.append(scale)
        agree.append(float(np.mean(
            np.asarray(tok) == np.asarray(sd.logits).argmax(-1))))
        if i + 1 < NEW_TOKENS:
            sd.step(tok)
            tok = sk.step(tok)
    out["session_logits"] = {"max_abs_err": max(errs),
                             "scale_min": min(scales),
                             "token_agreement": float(np.mean(agree))}
    return out


def contexts(L):
    """Per target cut: (label, predicates in order of preference,
    candidate (device, channel, weights, accuracy budget) contexts). The
    planner picks every cut; the smoke keeps the first context whose
    plan meets the first predicate any context meets.

    Random weights give near-flat logits, the worst case for the noise
    budget, so only loose accuracy budgets bring layers to <= 8 bits
    (the wire structs) and <= 4 bits (int4 packing, which needs every
    deployed layer at <= 4 bits: the stacked layers share one layout)."""
    from repro.core.cost_model import Channel, DeviceProfile, ObjectiveWeights
    ch = Channel(capacity_bps=200e6)
    cheap_server = ObjectiveWeights()            # server compute ~free
    # server compute priced high: keeping blocks on the device wins, up
    # to what the device's memory holds (weights + its KV cache)
    dear_server = ObjectiveWeights(eta=1e7)
    big = DeviceProfile(memory_bytes=4e9)
    return [
        ("offload", [lambda p, bits: p == 0],
         [(big, ch, cheap_server, 0.01)]),
        ("mid", [lambda p, bits: 0 < p < L and max(bits) <= 4,
                 lambda p, bits: 0 < p < L and max(bits) <= 8],
         [(DeviceProfile(memory_bytes=m), ch, dear_server, a)
          for a in (0.95, 0.8, 0.5)
          for m in (20e6, 40e6, 80e6, 10e6, 160e6)]),
        ("edge", [lambda p, bits: p == L and min(bits) <= 4 < max(bits),
                  lambda p, bits: p == L and min(bits) <= 4],
         [(big, ch, dear_server, a) for a in (0.5, 0.8, 0.95, 0.99)]),
    ]


def plan_for(srv, name, label, accepts, candidates):
    from repro.serving.simulator import InferenceRequest
    seen = []
    for accept in accepts:
        for dev, ch, w, budget in candidates:
            srv.build_store(name, dev, ch, w)
            req = InferenceRequest(name, budget, dev, ch, w, batch=BATCH,
                                   segment_cached=True,
                                   max_new_tokens=NEW_TOKENS)
            dep = srv.serve(req)
            bits = [int(b) for b in dep.extra["bits_w"]]
            if accept(dep.plan.p, bits or [99]):
                print(f"[plan] {label}: p={dep.plan.p} bits_w={bits} "
                      f"bits_x={dep.plan.bits_x:.2f} budget={budget} "
                      f"device_memory={dev.memory_bytes:.0f} eta={w.eta:g}",
                      flush=True)
                return dep, bits
            seen.append((dev.memory_bytes, budget, dep.plan.p,
                         sorted(set(bits))))
    print(f"[plan] {label}: contexts tried (memory, budget, p, bits): {seen}")
    raise AssertionError(f"no context made the planner pick {label!r}")


def run(seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.models import transformer as T
    from repro.serving.backends import TransformerBackend
    from repro.serving.qpart_server import QPARTServer

    times = {}
    cfg = get_config("smollm-135m")
    L = cfg.num_layers
    name = cfg.name
    print(f"[model] {name}: layers={L} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype}")

    with phase("setup: init params", times):
        params = jax.jit(lambda k: T.init_params(k, cfg))(
            jax.random.key(seed))
        jax.block_until_ready(params)
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, (N_CALIB + N_TEST, PROMPT))
    toks = jnp.asarray(toks, jnp.int32)
    calib_x, test_x = toks[:N_CALIB], toks[N_CALIB:]
    prompt = test_x[:BATCH]

    backend = TransformerBackend(cfg, params, seq_len=PROMPT,
                                 decode_max_len=MAX_LEN)
    with phase("setup: labels (forward, includes compile)", times):
        calib_y = jnp.argmax(backend.forward(calib_x), -1)
        test_y = jnp.argmax(backend.forward(test_x), -1)
        jax.block_until_ready(test_y)

    srv = QPARTServer(levels=LEVELS)
    srv.register(name, backend, calib_x, calib_y)
    with phase("calibrate (includes compile)", times):
        srv.calibrate(name)
    m = srv.models[name]
    assert np.all(np.isfinite(m.s_w)) and np.all(np.isfinite(m.s_x)), \
        "calibration produced NaN"
    print(f"[calibrate] base accuracy={m.base_accuracy}")

    results = {}
    for label, accepts, candidates in contexts(L):
        with phase(f"{label}: build_store + serve", times):
            dep, bits = plan_for(srv, name, label, accepts, candidates)
        r = {"p": int(dep.plan.p), "bits_w": bits,
             "bits_x": float(dep.plan.bits_x)}
        with phase(f"{label}: execute (first call includes compile)",
                   times):
            res = dep.execute(test_x, test_y)
        assert np.isfinite(res.accuracy_degradation), "execute gave NaN"
        r["accuracy"] = float(res.accuracy)
        r["accuracy_degradation"] = float(res.accuracy_degradation)

        sess = dep.decode_session()
        r["device_weights"] = wire_kind(sess, bits)
        with phase(f"{label}: compile decode programs", times):
            r["pallas_calls"] = count_kernels(backend, sess, prompt)
        with phase(f"{label}: generate (first call includes compile)",
                   times):
            out = dep.generate(prompt, NEW_TOKENS)
        assert out.tokens.shape == (BATCH, NEW_TOKENS), out.tokens.shape
        assert np.all((out.tokens >= 0) & (out.tokens < cfg.vocab_size))
        r["generate_s"] = {"ttft": out.ttft_s, "total": out.t_total_s,
                           "device": out.t_device_s,
                           "server": out.t_server_s}
        r["device_cache_dtype"] = out.device_cache_dtype
        with phase(f"{label}: reference check", times):
            r["check"] = (compare_p0(backend, dep, prompt) if dep.plan.p == 0
                          else compare_kernels(backend, dep, prompt))
        print(f"[result] {label}: {json.dumps(r)}", flush=True)
        results[label] = r

    kinds = {r["device_weights"] for r in results.values()}
    assert {"int4", "int8"} <= kinds, f"kernels run: {kinds}"
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[memory] peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(f"[phases] {json.dumps(times)}")
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = require_tpu()
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.compile_cache import use_compile_cache
    import jax
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__}")
    print(f"[compile cache] {use_compile_cache()}")
    check_lanes()
    run(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
