"""Record the small chip trace that ``test_trace.py`` reduces.

  python3 bench/tests/make_trace_fixture.py     # on the chip

Traces, inside the benchmark's own spans, one request's worth of the
served path's device work at toy sizes: a jitted matmul, the program's
int8 ``qmatmul`` and decode-attention kernels, and an idle gap. Writes
``bench/tests/data/chip_trace.xplane.pb`` (a few hundred KB) and the
expected intervals beside it.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from bench.core.trace import Tracer

    if jax.devices()[0].platform != "tpu":
        sys.exit("make_trace_fixture: needs the chip")
    x = jnp.ones((8, 512), jnp.bfloat16)
    w = {"codes": jnp.ones((512, 256), jnp.uint8),
         "scale": jnp.full((1, 1), 0.01, jnp.float32),
         "mu": jnp.zeros((1, 1), jnp.float32)}
    q = jnp.ones((1, 4, 4, 64), jnp.bfloat16)
    ck = jnp.ones((1, 512, 4, 64), jnp.bfloat16)
    mm = jax.jit(lambda a: (a @ a.T).sum())
    qm = jax.jit(lambda a: ops.qdense(a, w))
    da = jax.jit(lambda a, k: ops.decode_attention(a, k, k, jnp.int32(7)))
    jax.block_until_ready((mm(x), qm(x), da(q, ck)))     # compile first
    tracer = Tracer(True)
    d = tempfile.mkdtemp(prefix="fixture-")
    jax.profiler.start_trace(d)
    with tracer.span("window"):
        with tracer.span("serve", request=0):
            jax.block_until_ready(mm(x))
        with tracer.span("generate", request=0):
            tracer.switch("prefill", request=0)
            jax.block_until_ready(qm(x))
            tracer.switch("decode", request=0, step=1)
            jax.block_until_ready(da(q, ck))
            tracer.switch(None)
        with tracer.span("arrival_wait", request=1):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    out = os.path.join(ROOT, "bench", "tests", "data")
    os.makedirs(out, exist_ok=True)
    shutil.copy(src, os.path.join(out, "chip_trace.xplane.pb"))
    shutil.rmtree(d, ignore_errors=True)
    print(json.dumps({"bytes": os.path.getsize(
        os.path.join(out, "chip_trace.xplane.pb"))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
