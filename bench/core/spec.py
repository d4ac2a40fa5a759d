"""Load a cell's specification: ``BENCHMARK.json`` plus the data files the
harness finds by name.

  bench/configs/<config>.json   model sizes (published keys), reduced,
                                assumed, set-up sizes, and the model
                                family (``"family"``; "dense" if absent)
  bench/families/<family>.py    what is model-shaped: sizes, the program's
                                config fields, weights from the seed and
                                their layout, the plain reference, the
                                rehearsal's stand-ins, the work counts
  bench/traffic/<traffic>.json  the traffic mix the generator reads
  bench/cells/<workload>.json   the cell's driver, fixed arrival rate and
                                correctness limits
  bench/drivers/<driver>.py     how the window drives the program
  bench/metrics/<metric>.py     one reader per per-layer metric

Adding a cell adds files; no existing file changes. A configuration of a
new kind of model adds its family as a file beside the others.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
DEFAULT_FAMILY = "dense"


class SpecError(RuntimeError):
    """A benchmark file is missing or inconsistent."""


def _read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing benchmark file {path}")
    with open(path) as f:
        return json.load(f)


_LOADED: dict = {}


def load_module(path: pathlib.Path, name: str):
    """Import one file by path, once per process (driver, family and
    metric files are found by the names in BENCHMARK.json, which may hold
    dots and dashes)."""
    if not path.is_file():
        raise SpecError(f"missing benchmark file {path}")
    key = path.resolve()
    if key not in _LOADED:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[key] = mod
    return _LOADED[key]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    model: dict        # bench/configs/<config>.json
    family: object     # bench/families/<family>.py, loaded
    traffic: dict      # bench/traffic/<traffic>.json
    cell: dict         # bench/cells/<name>.json
    end_to_end: list   # BENCHMARK.json end_to_end entries this cell reports
    per_layer: list    # BENCHMARK.json per_layer entries this cell reports

    @property
    def driver(self) -> str:
        return self.cell["driver"]

    @property
    def rate_per_s(self) -> float:
        return float(self.cell["rate_per_s"])


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    model = _read_json(root / configs[w["config"]]["file"])
    family = family_module(model.get("family", DEFAULT_FAMILY), root)
    traffic = _read_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    cell = _read_json(root / "bench" / "cells" / f"{workload}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload)]
    return Cell(workload, int(w["chips"]), model, family, traffic, cell, e2e,
                per_layer)


def metric_reader(name: str, root: pathlib.Path = ROOT):
    """The reader module of per-layer metric ``name``."""
    return load_module(root / "bench" / "metrics" / f"{name}.py",
                       f"bench_metric_{name.replace('.', '_')}")


def family_module(name: str, root: pathlib.Path = ROOT):
    """The family module a configuration names."""
    return load_module(root / "bench" / "families" / f"{name}.py",
                       f"bench_family_{name.replace('.', '_')}")


def driver_module(name: str, root: pathlib.Path = ROOT):
    return load_module(root / "bench" / "drivers" / f"{name}.py",
                       f"bench_driver_{name.replace('.', '_')}")


def rehearsal_sizes(family, model: dict, traffic: dict,
                    widths: str = "toy") -> tuple[dict, dict]:
    """Small stand-ins for a CPU rehearsal of the whole run: same keys and
    code paths, the family's stand-in sizes for the model (toy widths for
    ``widths="toy"``; ``"wide"``: a few layers at the published widths),
    short prompts and answers, a small calibration set. Never used on the
    measuring path."""
    if widths not in ("toy", "wide"):
        raise SpecError(f"unknown rehearsal widths {widths!r}")
    m = family.rehearsal(model, widths)
    m["setup"] = dict(model["setup"], decode_max_len=256, calib_sequences=4,
                      calib_len=32, test_sequences=4)
    t = json.loads(json.dumps(traffic))
    t["prompt_len"]["buckets"] = [16, 32, 64]
    t["output_len"].update(median=8, min=2, max=16)
    return m, t
