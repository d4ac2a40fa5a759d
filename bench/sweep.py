"""The knee sweep: one cell's set-up once, then one window at each of
several fixed arrival rates, in one process, with no reference check.

The knee is the highest swept rate at which the wait before service
does not grow: the median request is served on arrival, its wait no
longer than the generator's own lateness (``WAIT_S``), at that rate and
at every lower one swept. Past it the median time to first token climbs
with the rate. A cell's rate is set once, at about 4/5 of the knee
(PERF.md), and then stays fixed in ``bench/cells/``.

  python3 bench/sweep.py --workload <cell> --rates 0.2,0.3,0.4 --seconds 51
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench.run import CompileCounter, log, open_process, parse  # noqa: E402

# a request that waits no longer than this was served on arrival: the
# generator wakes within about a millisecond of a due time (PERF.md)
WAIT_S = 0.01


def knee(rows) -> float | None:
    """The highest rate, in ascending order, before the first whose
    median request waits longer than ``WAIT_S``."""
    best = None
    for row in sorted(rows, key=lambda r: r["rate_per_s"]):
        if row["wait_s_median"] is None or row["wait_s_median"] > WAIT_S:
            break
        best = row["rate_per_s"]
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    args = parse(["--workload", a.workload, "--seed", str(a.seed),
                  "--seconds", str(a.seconds), "--trace", "0"]
                 + (["--rehearse"] if a.rehearse else []))
    from bench.core import spec
    cell = spec.load_cell(a.workload)
    model, traffic = cell.model, cell.traffic
    if a.rehearse:
        model, traffic = spec.rehearsal_sizes(cell.family, model, traffic)
    devs = open_process(args, cell)
    counter = CompileCounter()

    from bench.core import program, run_view, trace
    from bench.core import traffic as traffic_lib

    system = program.build(cell.family, model, traffic, a.seed, log)
    program.warm_up(system, a.seed)
    driver = spec.driver_module(cell.driver)
    rows = []
    for rate in sorted(float(r) for r in a.rates.split(",")):
        requests = traffic_lib.schedule(traffic, rate, a.seconds, a.seed,
                                        system.cfg.vocab_size)
        before = counter.snapshot()
        t0, records = driver.run(system, requests, a.seconds,
                                 trace.Tracer(False))
        view = run_view.RunView(records, t0, a.seconds,
                                cell.family.dims(model),
                                devs[0].device_kind, cell.family)
        row = {"workload": a.workload, "rate_per_s": rate,
               **run_view.window_summary(records, t0, a.seconds),
               **run_view.end_to_end(view, t0 + a.seconds + driver.DRAIN_S),
               "compiles_in_window": {
                   k: v - before.get(k, 0)
                   for k, v in counter.snapshot().items()
                   if v - before.get(k, 0)}}
        rows.append(row)
        print("[sweep] " + json.dumps(row), flush=True)
    k = knee(rows)
    print("[sweep] " + json.dumps(
        {"workload": a.workload, "knee_per_s": k,
         "rate_per_s": None if k is None else 0.8 * k}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
