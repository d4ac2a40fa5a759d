"""The model-family seam: a configuration names its family
(``"family"``, "dense" where absent), the harness loads
``bench/families/<family>.py`` by path, and everything model-shaped
(weights, layout, reference, rehearsal sizes, work counts) comes from
that file.

The dense family's weights, reference logits and the share readers'
values are locked to the numbers the harness gave before the family
seam existed: digests and values recorded from that code on the CPU
backend, at the toy rehearsal sizes and a seed above 32 bits. A probe
family, written only as files into a copy of the benchmark tree, is
shown to be the one the whole run calls."""
from __future__ import annotations

import hashlib
import json
import os
import pathlib
import shutil
import types

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from bench import run  # noqa: E402
from bench.core import program, spec, trace, weights  # noqa: E402
from bench.core.program import Plan  # noqa: E402

CELLS = ("smollm-135m.edge", "qwen1.5-4b.edge")
SEED = 2 ** 33 + 12345
FIXTURE = pathlib.Path(__file__).parent / "data" / "chip_trace.xplane.pb"
SHARES = ("decode_mfu", "qmatmul_roofline", "decode_attn_roofline")

# sha256 over (shape, dtype, bytes) of every leaf, in tree order
DIGESTS = {
    "smollm-135m.edge": {
        "params": "fa05347e4e9d9824981ba8743f26ea7c"
                  "9ab8995431f0482296b95cc333708f8a",
        "published": "798a8c795229a0d15c3bc20c03bbcc9b"
                     "3a27c4a68937050b650641d86a06639b",
        ("logits", 2, "f32"): "a39595dad646c450c53e72da8c42e4cd"
                              "57a21f18e765f7d6cc86c8bc2eabd34f",
        ("logits", 2, "fp8"): "fd188b15e8db302ead585b822017cd2e"
                              "5c7194ff504d565317632016ee9b9bf0",
        ("logits", 1, "f32"): "361c41aad957e7434aeef8c91ed564b8"
                              "5b3e07d361d8af7ba434d843a22b5d5f",
        ("logits", 0, "f32"): "6c05ffdbb62e08850dde361b1af8d8dc"
                              "a2c1a136f87b803a1247d0f45b94baa1",
    },
    "qwen1.5-4b.edge": {
        "params": "bee10c4a1c9d223c07d39e69ef881afa"
                  "201dab91676cd80eeb285ed3c7fca943",
        "published": "3a74ec764e85e69c468e4a017d07ee46"
                     "9f596407ae022135499ab7d41953f38a",
        ("logits", 2, "f32"): "61785ebb79bc2ed9fd4524f61427524c"
                              "fc123b10040bee20d8dccc49116b3a3f",
        ("logits", 2, "fp8"): "08c02f1ad38dad280c38e2e2620dd9be"
                              "53aeea0ea3214d9e41e8c6e02e694e6f",
        ("logits", 1, "f32"): "edc3c6e9172b7011dcbad1107ef25a62"
                              "72da3c52e37c8732c36cc8cc75ba6cc7",
        ("logits", 0, "f32"): "0d3eb85be846afb310b3741ad89362f0"
                              "952a66548e1172e66fc37cd20c5ed705",
    },
}
HOP_BITS = {2: 6, 1: 9, 0: 0}           # cut p -> bits_x of the plan

# the share readers on the fixture, at each config's published sizes
# under a whole-stack plan of (weight bits, hop bits)
PLAN_BITS = {"smollm-135m.edge": (6, 9), "qwen1.5-4b.edge": (8, 8)}
FIXTURE_SHARES = {
    "smollm-135m.edge": {"decode_mfu": 0.25036328713497297,
                         "qmatmul_roofline": 39833.054074638225,
                         "decode_attn_roofline": 6.642707941409238},
    "qwen1.5-4b.edge": {"decode_mfu": 1.3119599807794218,
                        "qmatmul_roofline": 154235.17982567474,
                        "decode_attn_roofline": 5.412576841148271},
}

PROBE = '''"""The dense family, counting each call."""
import collections
import pathlib

from bench.core.spec import load_module

_dense = load_module(pathlib.Path(__file__).with_name("dense.py"),
                     "bench_family_probe_dense")
CALLS = collections.Counter()
COUNTED = ("dims", "program_fields", "published", "to_program",
           "rehearsal", "weights_kind", "decode_flops", "routed_matmuls",
           "attention_layers")


def _counted(name):
    def call(*args, **kwargs):
        CALLS[name] += 1
        return getattr(_dense, name)(*args, **kwargs)
    return call


for _name in COUNTED:
    globals()[_name] = _counted(_name)


class Reference(_dense.Reference):
    def __init__(self, *args, **kwargs):
        CALLS["Reference"] += 1
        super().__init__(*args, **kwargs)

    def logits(self, *args, **kwargs):
        CALLS["Reference.logits"] += 1
        return super().logits(*args, **kwargs)
'''


def digest(tree) -> str:
    import jax
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        a = np.asarray(leaf)
        h.update(str((a.shape, a.dtype.str)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module", autouse=True)
def _program_on_path():
    run.prepare_process(rehearse=True)


@pytest.fixture(scope="module")
def tv():
    return trace.reduce(str(FIXTURE))


def _toy(cell):
    return spec.rehearsal_sizes(cell.family, cell.model, cell.traffic,
                                "toy")[0]


def _fixture_view(tv, model, family, bits):
    L = model["num_hidden_layers"]
    plan = Plan(L, (bits[0],) * L, bits[1])
    rec = types.SimpleNamespace(index=0, prompt_len=8, serve_start=0.0,
                                serve_end=0.001, plan=plan,
                                token_times=[0.0, 1.0])
    return types.SimpleNamespace(trace=tv, records=[rec],
                                 dims=family.dims(model),
                                 device_kind="TPU v5 lite", family=family)


@pytest.mark.parametrize("name", CELLS)
def test_config_without_family_is_dense(name):
    cell = spec.load_cell(name)
    assert "family" not in cell.model
    assert pathlib.Path(cell.family.__file__) == \
        spec.BENCH_DIR / "families" / "dense.py"
    assert cell.family.dims(cell.model)["L"] == \
        cell.model["num_hidden_layers"]


def test_unknown_family_is_a_spec_error(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.family_module("no-such-family", tmp_path)


@pytest.mark.parametrize("name", CELLS)
def test_dense_weights_are_the_parents(name):
    cell = spec.load_cell(name)
    m = _toy(cell)
    cfg = program.program_config(cell.family, m)
    want = DIGESTS[name]
    assert digest(weights.make_program_params(SEED, m, cfg, cell.family)) \
        == want["params"]
    assert digest(weights.make_published(SEED, m, cell.family.published)) \
        == want["published"]


@pytest.mark.parametrize("name", CELLS)
def test_dense_reference_is_the_parents(name):
    cell = spec.load_cell(name)
    m = _toy(cell)
    ref = cell.family.Reference(m, SEED, 24, 8)
    tokens = (np.arange(20) * 37 + 5) % m["vocab_size"]
    rows = np.arange(12, 20)
    for key, want in DIGESTS[name].items():
        if key[0] != "logits":
            continue
        _, p, compute = key
        got = ref.logits(tokens, rows, p, [5, 7], HOP_BITS[p], compute)
        assert digest(got) == want, key


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("reader", SHARES)
def test_share_readers_are_the_parents_on_the_fixture(tv, name, reader):
    cell = spec.load_cell(name)
    view = _fixture_view(tv, cell.model, cell.family, PLAN_BITS[name])
    assert spec.metric_reader(reader).read(view) == \
        FIXTURE_SHARES[name][reader]


@pytest.fixture(scope="module")
def probe_root(tmp_path_factory):
    """A copy of the benchmark's files with one more configuration and
    cell, of a family that exists only in the copy."""
    root = tmp_path_factory.mktemp("tree")
    shutil.copy(spec.ROOT / "BENCHMARK.json", root)
    for d in ("configs", "cells", "traffic", "drivers", "metrics",
              "families"):
        shutil.copytree(spec.BENCH_DIR / d, root / "bench" / d)
    (root / "bench" / "families" / "probe.py").write_text(PROBE)
    model = json.loads((spec.BENCH_DIR / "configs" /
                        "smollm-135m.json").read_text())
    model.update(name="probe-lm", family="probe")
    (root / "bench" / "configs" / "probe-lm.json").write_text(
        json.dumps(model))
    shutil.copy(spec.BENCH_DIR / "cells" / "smollm-135m.edge.json",
                root / "bench" / "cells" / "probe-lm.edge.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "probe-lm", "source": "probe",
                             "file": "bench/configs/probe-lm.json",
                             "reduced": [], "why": "probe"})
    bench["workloads"].append({"name": "probe-lm.edge", "config": "probe-lm",
                               "traffic": "edge", "chips": 1,
                               "why": "probe"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_probe_family_is_built_checked_and_counted(probe_root, tv):
    cell = spec.load_cell("probe-lm.edge", probe_root)
    probe = cell.family
    assert pathlib.Path(probe.__file__) == \
        probe_root / "bench" / "families" / "probe.py"
    probe.CALLS.clear()
    args = run.parse(["--workload", "probe-lm.edge", "--seed", "7",
                      "--seconds", "3", "--trace", "0", "--rehearse",
                      "toy"])
    res = run.run_cell(args, root=probe_root)
    assert res["correct"], res["checks"]
    for name in ("rehearsal", "program_fields", "published", "to_program",
                 "weights_kind", "dims", "Reference", "Reference.logits"):
        assert probe.CALLS[name] > 0, (name, dict(probe.CALLS))
    view = _fixture_view(tv, cell.model, probe,
                         PLAN_BITS["smollm-135m.edge"])
    values = {r: spec.metric_reader(r, probe_root).read(view)
              for r in SHARES}
    assert values == FIXTURE_SHARES["smollm-135m.edge"]
    for name in ("decode_flops", "routed_matmuls", "attention_layers"):
        assert probe.CALLS[name] > 0, (name, dict(probe.CALLS))
    assert not (spec.BENCH_DIR / "families" / "probe.py").exists()
    assert not (spec.BENCH_DIR / "configs" / "probe-lm.json").exists()
