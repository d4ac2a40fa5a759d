"""Read the numbers that set ``correct``'s limits: the program's widest
served-token logit gap over many seeds (the lower reading) and the
float8 control's on the same prompts and tokens (the upper reading).

  python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

One process, one short window per seed at the cell's own load, each
followed by the reference and the control. Prints one JSON line per
seed and a summary line. The control's numbers go through the same
comparison as the program's (``run.passes``); exits 1 where the control
comes out correct on any seed or the program does not. The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench.run import CompileCounter, parse, prepare_process, run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", nargs="?", const="toy",
                    choices=("toy", "wide"))
    a = ap.parse_args(argv)
    prepare_process(a.rehearse)
    counter = CompileCounter()
    rows = []
    for seed in (int(s) for s in a.seeds.split(",")):
        args = parse(["--workload", a.workload, "--seed", str(seed),
                      "--seconds", str(a.seconds), "--trace", "0"]
                     + (["--rehearse", a.rehearse] if a.rehearse else []))
        res = run_cell(args, control=True, counter=counter)
        row = {"seed": seed, "served": res["attempted"] - res["failed"],
               "correct": res["correct"],
               "control_correct": res["control_correct"],
               **{k: v["value"] for k, v in res["checks"].items()}}
        rows.append(row)
        print("[control] " + json.dumps(row), flush=True)
    keys = [k for k in rows[0] if k not in ("seed", "served", "correct")
            and not k.startswith("control_")]
    summary = {"workload": a.workload}
    for k in keys:
        summary[k] = {"lower": max(r[k] for r in rows),
                      "upper": min(r["control_" + k] for r in rows)}
    summary["program_correct"] = all(r["correct"] for r in rows)
    summary["control_correct"] = any(r["control_correct"] for r in rows)
    print("[control] summary " + json.dumps(summary), flush=True)
    return 0 if summary["program_correct"] and not summary[
        "control_correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
