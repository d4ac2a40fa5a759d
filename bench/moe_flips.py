"""How often the program's top-k expert sets differ from the reference's
(the near-tie hazard of comparing a routed model): a diagnostic for
PERF.md, never run by the benchmark's own runs.

  python3 bench/moe_flips.py --workload moonlight-16b-a3b.edge \\
      --seeds 1,2,3 --bits 6 --bits-x 8

One process. For each seed: the program's weights and the family's
reference from the seed, and one sequence (the traffic's longest prompt
plus its longest answer, ids drawn from the seed). The program prefills
the sequence through its served path (``DecodeSession`` at p = L, every
layer at ``--bits``, the hop at ``--bits-x``: the extend program the
window's prefills run, through the compiled kernels on the chip) with
the router's choices recorded as they are made (``jax.debug.callback``
around ``models.moe.route``); the reference's router choices over the
same sequence and plan come from ``Reference.routes``. Prints, per seed
and in all, the share of (position, expert layer) whose sets differ, and
the share of the program's (token, expert) pairs that land on the
experts this chip holds (what the ``moe.pairs_here`` / ``moe.pairs``
counters read; 8 / 64 for an even router).
``--rehearse`` runs the family's toy sizes on any backend.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench.run import prepare_process  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--bits", type=int, default=6)
    ap.add_argument("--bits-x", type=int, default=8)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    prepare_process(a.rehearse)
    import jax
    import numpy as np

    from bench.core import program, spec
    from bench.core.weights import make_program_params
    from repro.core.solver import PartitionPlan
    from repro.models import moe as moe_lib
    from repro.serving.backends import TransformerBackend
    from repro.serving.decode import DecodeSession

    cell = spec.load_cell(a.workload)
    model, traffic = cell.model, cell.traffic
    if a.rehearse:
        model, traffic = spec.rehearsal_sizes(cell.family, model, traffic)
    fam = cell.family
    n = fam.dims(model)
    cfg = program.program_config(fam, model)
    L = cfg.num_layers
    s = max(traffic["prompt_len"]["buckets"]) + traffic["output_len"]["max"]
    chosen = []
    route = moe_lib.route

    def recorded(params, cfg, x2):
        ids, gates = route(params, cfg, x2)
        jax.debug.callback(lambda v: chosen.append(np.asarray(v)), ids,
                           ordered=True)
        return ids, gates

    moe_lib.route = recorded
    plan = PartitionPlan(p=L, bits_w=np.full(L, float(a.bits)),
                         bits_x=float(a.bits_x), objective=0.0,
                         psi_total=0.0, payload_bits=0.0, breakdown={})
    flips = rows = here = pairs = 0
    held = np.arange(n["first_held"], n["first_held"] + n["held"])
    for seed in (int(v) for v in a.seeds.split(",")):
        params = make_program_params(seed, model, cfg, fam)
        backend = TransformerBackend(cfg, params, seq_len=s,
                                     decode_max_len=s + 1)
        seq = np.random.default_rng([seed, 9]).integers(
            0, n["V"], s).astype(np.int32)
        chosen.clear()
        sess = DecodeSession(backend, plan, max_len=s + 1)
        jax.block_until_ready(sess.prefill(seq[None]))
        jax.effects_barrier()
        ref = fam.Reference(model, seed, s, s)
        want = ref.routes(seq, np.arange(s), L, [a.bits] * L, a.bits_x)
        got = dict(zip(fam.moe_layers(n), chosen))
        differ = sum(int(np.sum(np.any(np.sort(got[layer], -1)
                                       != np.sort(want[layer], -1), -1)))
                     for layer in want)
        total = s * len(want)
        on = sum(int(np.isin(ids, held).sum()) for ids in got.values())
        k = sum(ids.size for ids in got.values())
        flips, rows = flips + differ, rows + total
        here, pairs = here + on, pairs + k
        print("[flips] " + json.dumps({"seed": seed, "rows": total,
                                       "differ": differ,
                                       "share": differ / total,
                                       "pairs_here_share": on / k}),
              flush=True)
        del params, backend, sess, ref
    print("[flips] " + json.dumps({"workload": a.workload, "bits": a.bits,
                                   "bits_x": a.bits_x, "rows": rows,
                                   "differ": flips,
                                   "share": flips / max(rows, 1),
                                   "pairs_here_share": here / max(pairs, 1)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
