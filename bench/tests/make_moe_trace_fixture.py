"""Record the chip trace that ``test_mla_moe.py`` reduces.

  python3 bench/tests/make_moe_trace_fixture.py     # on the chip

Moonlight-16B-A3B's two-layer stand-in at its published widths (the
family's ``"wide"`` rehearsal: the dense layer and one expert layer of 8
held experts; random weights from a fixed seed), one ``DecodeSession``
at p = L with 6-bit wire-struct weights through the compiled kernels:
the prefill of a 128-token prompt and three decode steps, inside the
benchmark's window and generate spans, after a warm-up that compiles
every program. Writes ``bench/tests/data/chip_moe_trace.xplane.pb.gz``
and prints the ``qpart.moe`` span's arguments and the kernel's calls.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

OUT = os.path.join(ROOT, "bench", "tests", "data",
                   "chip_moe_trace.xplane.pb.gz")


def main() -> int:
    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        sys.exit("make_moe_trace_fixture: needs the chip")
    from bench.core import program, program_trace, spec, trace
    from bench.core.weights import make_program_params
    from repro.core.solver import PartitionPlan
    from repro.serving.backends import TransformerBackend
    from repro.serving.decode import DecodeSession

    cell = spec.load_cell("moonlight-16b-a3b.edge")
    model = cell.family.rehearsal(cell.model, "wide")
    cfg = program.program_config(cell.family, model)
    max_len = int(model["setup"]["decode_max_len"])
    backend = TransformerBackend(
        cfg, make_program_params(1, model, cfg, cell.family), seq_len=128,
        decode_max_len=max_len)
    L = cfg.num_layers
    plan = PartitionPlan(p=L, bits_w=np.full(L, 6.0), bits_x=8.0,
                         objective=0.0, psi_total=0.0, payload_bits=0.0,
                         breakdown={})
    seg = backend.split(plan)
    prompt = (np.arange(128, dtype=np.int32) * 7 % cfg.vocab_size)[None]

    def generate():
        DecodeSession(backend, plan, max_len=max_len,
                      segment=seg).generate(prompt, 4)

    generate()                                   # compile first
    tracer = trace.Tracer(True)
    d = tempfile.mkdtemp(prefix="fixture-")
    jax.profiler.start_trace(d)
    with tracer.span("window"), tracer.span("generate", request=0):
        generate()
    jax.profiler.stop_trace()
    (src,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    with open(src, "rb") as f, gzip.open(OUT, "wb") as g:
        shutil.copyfileobj(f, g)
    tv = trace.reduce(src)
    pv = program_trace.reduce(src, tv.offset_ns)
    shutil.rmtree(d, ignore_errors=True)
    kernel = spec.metric_reader("moe_roofline").is_kernel
    calls = [(trace.short_name(n, 100), int(t)) for n, t in
             zip(tv.op_names, tv.op_dur) if kernel(n)]
    print(json.dumps({"bytes": os.path.getsize(OUT),
                      "moe": [s.args for s in pv.of("moe")],
                      "steps": len(pv.of("step")), "kernel_calls": calls}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
