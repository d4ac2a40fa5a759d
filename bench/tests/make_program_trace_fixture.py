"""Record the served-path chip trace that ``test_program_trace.py`` reduces.

  python3 bench/tests/make_program_trace_fixture.py     # on the chip

SmolLM-135M at its published widths (random weights from a fixed
seed), one ``DecodeSession`` at p = L with 6-bit wire-struct weights
through the compiled kernels: the prefill of a 128-token prompt and two
decode steps, inside the benchmark's window span, after a warm-up that
compiles every program. Writes
``bench/tests/data/chip_program_trace.xplane.pb.gz`` and prints where
the program's spans put each program's device time.
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
                   "chip_program_trace.xplane.pb.gz")


def main() -> int:
    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        sys.exit("make_program_trace_fixture: needs the chip")
    from bench.core import program, program_trace, spec, trace
    from bench.core.weights import make_program_params
    from repro.core.solver import PartitionPlan
    from repro.serving.backends import TransformerBackend
    from repro.serving.decode import DecodeSession

    cell = spec.load_cell("smollm-135m.edge")
    model = cell.model
    cfg = program.program_config(cell.family, model)
    max_len = int(model["setup"]["decode_max_len"])
    backend = TransformerBackend(
        cfg, make_program_params(1, model, cfg, cell.family), seq_len=128,
        decode_max_len=max_len)
    L = cfg.num_layers
    plan = PartitionPlan(p=L, bits_w=np.full(L, 6.0), bits_x=9.0,
                         objective=0.0, psi_total=0.0, payload_bits=0.0,
                         breakdown={})
    seg = backend.split(plan)
    prompt = (np.arange(128, dtype=np.int32) % cfg.vocab_size)[None]

    def generate():
        DecodeSession(backend, plan, max_len=max_len,
                      segment=seg).generate(prompt, 3)

    generate()                                   # compile first
    d = tempfile.mkdtemp(prefix="fixture-")
    jax.profiler.start_trace(d)
    with trace.Tracer(True).span("window"):
        generate()
    jax.profiler.stop_trace()
    (src,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    with open(src, "rb") as f, gzip.open(OUT, "wb") as g:
        shutil.copyfileobj(f, g)
    tv = trace.reduce(src)
    pv = program_trace.reduce(src, tv.offset_ns)
    shutil.rmtree(d, ignore_errors=True)
    rows = [(m.name, "/".join(m.path), m.dur) for m in pv.modules]
    print(json.dumps({"bytes": os.path.getsize(OUT), "runs": rows,
                      "spans": {k: len(v) for k, v in pv.program.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
