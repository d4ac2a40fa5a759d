"""Run one benchmark cell once on the chip.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: set-up (weights from the seed, calibration, offline stores,
warm-up of every shape the window uses), then an open-loop window of
``--seconds`` driven by the cell's driver, then the check of what the
window served against the plain reference. ``--trace 1`` records a
profiler trace of the window and reports the cell's per-layer metrics;
``--trace 0`` reports its end-to-end metrics. Everything but the result
goes on earlier lines; the last line of standard output is the result
as one JSON object, and the numbers compared for ``correct`` are the
last lines of standard error.

Exits non-zero, printing no result, off the TPU, with fewer chips than
the cell asks for, or outside the compiled-kernel lane. ``--rehearse``
runs the same code at toy sizes on any backend (kernels in interpret
mode; ``--rehearse wide``: four layers at the published widths) and is
never a measurement.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

_T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(msg: str) -> None:
    print(msg, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", nargs="?", const="toy",
                    choices=("toy", "wide"),
                    help="toy sizes ('wide': four layers at the published "
                    "widths) on any backend; not a measurement")
    return ap.parse_args(argv)


def prepare_process(rehearse) -> None:
    """Environment the program reads, set before JAX is imported: the
    compile cache in the checkout, the program's sources on the path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    if rehearse:
        os.environ.setdefault("REPRO_KERNELS", "interpret")
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def require_chip(chips: int):
    """The device the cell measures, or exit: no fallback to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: no TPU: JAX found platform {devs[0].platform!r} "
                 f"({devs[0].device_kind}); the benchmark measures the chip "
                 f"only")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX found "
                 f"{len(devs)}")
    from repro.kernels import ops
    from repro.models import attention
    if ops.kernel_mode() != "kernel" or attention._attention_impl() != "flash":
        sys.exit(f"bench: kernel lane is {ops.kernel_mode()!r} and attention "
                 f"{attention._attention_impl()!r}; the chip path runs the "
                 f"compiled kernels ('kernel', 'flash')")
    return devs


class CompileCounter:
    """Counts JAX's compile events (a persistent-cache hit emits none of
    the backend-compile kind)."""

    def __init__(self):
        import jax
        self.counts: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if "compile" in event:
            self.counts[event] = self.counts.get(event, 0) + 1

    def snapshot(self) -> dict:
        return dict(self.counts)


def open_process(args, cell):
    """Set up JAX for one process of runs: the persistent compile cache
    in the checkout with every program kept, and the chip the cell asks
    for (any backend under ``--rehearse``). -> the devices."""
    prepare_process(args.rehearse)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices() if args.rehearse else require_chip(cell.chips)
    dev = devs[0]
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} jax={jax.__version__} "
        f"rehearse={bool(args.rehearse)}")
    return devs


def passes(chk: dict, limits: dict, prefix: str = "") -> bool:
    """The comparison that decides ``correct``: every number compared is
    finite and within its limit. ``prefix`` "control_" reads the
    control's numbers."""
    import numpy as np
    return bool(chk["tokens"] > 0 and all(
        np.isfinite(chk[prefix + k]) and chk[prefix + k] <= float(v)
        for k, v in limits.items()))


def run_cell(args, control: bool = False, counter=None, root=None) -> dict:
    """One run of one cell. Returns the result object (the last stdout
    line's), with ``checks`` holding the numbers compared; with
    ``control`` also the control's numbers and ``control_correct``, the
    same comparison applied to them. ``root``: the tree whose benchmark
    files are read (default: this checkout)."""
    from bench.core import spec
    root = root or spec.ROOT
    cell = spec.load_cell(args.workload, root)
    model, traffic = cell.model, cell.traffic
    if args.rehearse:
        model, traffic = spec.rehearsal_sizes(cell.family, model, traffic,
                                              args.rehearse)
    devs = open_process(args, cell)
    dev = devs[0]
    import jax
    import numpy as np
    counter = counter or CompileCounter()

    from bench.core import correct, program, run_view, trace
    from bench.core import traffic as traffic_lib

    t = time.perf_counter()
    system = program.build(cell.family, model, traffic, args.seed, log)
    log(f"[setup] build (weights, labels, calibration, stores, plans) "
        f"{time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    n_warm = program.warm_up(system, args.seed)
    log(f"[setup] warm-up {n_warm} generations {time.perf_counter() - t:.3f}"
        f" s")
    requests = traffic_lib.schedule(traffic, cell.rate_per_s, args.seconds,
                                    args.seed, system.cfg.vocab_size)
    driver = spec.driver_module(cell.driver, root)
    tracer = trace.Tracer(bool(args.trace))
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    gc.collect()
    setup_s = time.perf_counter() - _T_START
    before = counter.snapshot()
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    try:
        t0, records = driver.run(system, requests, args.seconds, tracer)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    compiles = {k: v - before.get(k, 0) for k, v in
                counter.snapshot().items() if v - before.get(k, 0)}
    drain_end = t0 + args.seconds + driver.DRAIN_S
    stats_mem = dev.memory_stats() or {}
    memory_peak = int(stats_mem.get("peak_bytes_in_use", 0))

    view = run_view.RunView(records, t0, args.seconds,
                            cell.family.dims(model), dev.device_kind,
                            cell.family)
    served = view.served
    failed = len(records) - len(served)
    late = [r.late_s for r in records if r.late_s is not None]
    log(f"[window] requests={len(records)} served={len(served)} "
        f"failed={failed} compiles_in_window={compiles or 0}")
    log(f"[generator] idle arrivals={len(late)} lateness_ms mean="
        f"{1e3 * float(np.mean(late)) if late else 0.0:.4f} max="
        f"{1e3 * float(np.max(late)) if late else 0.0:.4f}")
    log(f"[plans] window histogram "
        f"{json.dumps(program.plan_histogram([r.plan for r in served]))}")
    log(f"[plans] accuracy_degradation "
        f"{json.dumps({str(k): v for k, v in system.accuracy.items()})}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result_extra = {}
    if args.trace:
        t = time.perf_counter()
        xplane = trace.find_xplane(trace_dir)
        log(f"[trace] {os.path.getsize(xplane)} bytes recorded")
        tv = trace.reduce(xplane)
        view.trace = tv
        summary = trace.device_summary(tv)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result_extra["breakdown"] = summary["breakdown"]
        metrics = {}
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"], root).read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        log(f"[trace] clock offset {tv.offset_ns} ns from {tv.n_paired} "
            f"program runs")
        log(f"[trace] ops={len(tv.op_names)} reduced in "
            f"{time.perf_counter() - t:.3f} s; busy_s={summary['busy_s']} "
            f"window_s={summary['window_s']}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        e2e = run_view.end_to_end(view, drain_end)
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
    log(f"[metrics] {json.dumps(metrics)}")

    # the check, after the window, with the program's state freed
    prompts = {r.index: r.prompt for r in requests}
    del system, view
    gc.collect()
    result = {"correct": False, "attempted": len(records), "failed": failed,
              "metrics": metrics, "device": device, **result_extra}
    live = sum(a.nbytes for a in jax.live_arrays())
    t = time.perf_counter()
    chk = correct.check(cell.family, model, args.seed, served, prompts,
                        traffic, control=control, log=log)
    log(f"[check] sampled requests={chk['requests']} tokens={chk['tokens']} "
        f"in {time.perf_counter() - t:.3f} s (live bytes before: {live}); "
        f"{json.dumps(chk)}")
    limits = cell.cell["limits"]
    keys = ("logit_gap", "mean_gap", "off_share") if control else limits
    checks = {}
    for key in keys:
        checks[key] = {"value": chk[key], "limit": limits.get(key)}
        if control:
            checks["control_" + key] = {"value": chk["control_" + key],
                                        "limit": limits.get(key)}
    result["correct"] = passes(chk, limits)
    if control:
        result["control_correct"] = passes(chk, limits, "control_")
    for c in checks.values():
        c["value"] = c["value"] if np.isfinite(c["value"]) else None
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    args = parse(argv)
    result = run_cell(args)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
