"""The mla_moe family (Moonlight-16B-A3B) in the harness: the config
file holds the catalog row's published keys with the cut ones listed,
the program's tree is the one the family lays out, a whole run at toy
sizes is ``correct``, and the two readers of the grouped expert kernel
read the committed chip trace of the served path
(``make_moe_trace_fixture.py``)."""
from __future__ import annotations

import gzip
import json
import os
import pathlib
import shutil
import tempfile
import types

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from bench import run  # noqa: E402
from bench.core import program, program_trace, spec, trace  # noqa: E402
from bench.core.peaks import least_time_s  # noqa: E402
from bench.core.program import Plan  # noqa: E402
from bench.core.spec import metric_reader  # noqa: E402

CELL = "moonlight-16b-a3b.edge"
FIXTURE = pathlib.Path(__file__).parent / "data" / \
    "chip_moe_trace.xplane.pb.gz"
CATALOG = {   # the catalog row's config (model-configs guide)
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16,
    "num_nextn_predict_layers": 0, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 163840}


@pytest.fixture(scope="module", autouse=True)
def _program_on_path():
    run.prepare_process(rehearse=True)


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


def test_config_is_the_catalog_row_with_its_cut(cell):
    m = cell.model
    changed = {k for k, v in CATALOG.items() if m[k] != v}
    assert changed == set(m["reduced"]) == set(m["published"])
    assert all(m["published"][k] == CATALOG[k] for k in changed)
    assert (m["num_hidden_layers"], m["n_routed_experts"],
            m["vocab_size"]) == (5, 8, 20480)
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    (entry,) = [c for c in bench["configs"] if c["name"] == m["name"]]
    assert entry["reduced"] == m["reduced"]
    assert m["deployment"]["chips_per_layer"] * m["n_routed_experts"] == \
        CATALOG["n_routed_experts"]
    assert CATALOG["vocab_size"] // m["deployment"]["chips_per_layer"] == \
        m["vocab_size"]


def test_program_config_is_at_published_widths(cell):
    cfg = program.program_config(cell.family, cell.model)
    a, e = cfg.mla, cfg.moe
    assert (cfg.d_model, cfg.num_heads, a.qk_nope_head_dim,
            a.qk_rope_head_dim, a.kv_lora_rank, a.v_head_dim) == \
        (2048, 16, 128, 64, 512, 128)
    assert (e.num_experts, e.held, e.top_k, e.d_ff, e.n_shared,
            cfg.d_ff, e.first_dense) == (64, 8, 6, 1408, 2, 11264, 1)
    assert (e.score, e.expert_bias, e.routed_scale) == \
        ("sigmoid", True, 2.446)


def test_whole_run_at_toy_sizes_is_checked():
    """The whole run at toy sizes: set-up through the program's entry
    points, the window, the end-to-end metrics and the comparison with
    the reference. Only run on the chip is ``correct`` read: at hidden 64
    in bfloat16 a router's near-tie flips move a toy's logits by as much
    as the limits (``moe_flips.py --rehearse`` reads ~10% of rows)."""
    args = run.parse(["--workload", CELL, "--seed", "3016000007",
                      "--seconds", "3", "--trace", "0", "--rehearse",
                      "toy"])
    res = run.run_cell(args)
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert {"itl_p95_ms", "tokens_per_s", "setup_s"} <= set(res["metrics"])
    assert all(np.isfinite(c["value"]) for c in res["checks"].values())


@pytest.fixture
def moe_trace(monkeypatch):
    """The committed trace where ``bench/run.py`` keeps a run's trace."""
    root = tempfile.mkdtemp(prefix="moe-trace-test-")
    monkeypatch.setattr(tempfile, "tempdir", root)
    d = os.path.join(root, "bench-trace-m", "plugins", "profile", "r")
    os.makedirs(d)
    path = os.path.join(d, "host.xplane.pb")
    with gzip.open(FIXTURE) as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    program_trace._cache.clear()
    yield path, trace.reduce(path)
    program_trace._cache.clear()
    shutil.rmtree(root, ignore_errors=True)


def _fixture_view(cell, tv):
    m = cell.family.rehearsal(cell.model, "wide")
    rec = types.SimpleNamespace(index=0, plan=Plan(2, (6, 6), 8),
                                prompt_len=128, token_times=[0.0] * 4)
    return types.SimpleNamespace(trace=tv, records=[rec],
                                 dims=cell.family.dims(m),
                                 device_kind="TPU v5 lite",
                                 family=cell.family)


def test_readers_on_the_fixture(cell, moe_trace):
    """One session of the two-layer stand-in at p = L, 6 bits: a 128-token
    prefill and three steps; the counters read once (pairs: 131 tokens x
    6 experts through the one expert layer), and both readers from the
    kernel's calls and those counters. In this trace the held experts
    got pairs in the prefill only (32 of 786; three kernel calls, none
    inside a step)."""
    path, tv = moe_trace
    pv = program_trace.reduce(path, tv.offset_ns)
    (moe,) = pv.of("moe")
    assert len(pv.of("step")) == 3
    assert moe.args["pairs"] == 131 * 6
    here = [int(v) for v in moe.args["pairs_here_by_layer"].split()]
    active = [int(v) for v in moe.args["experts_active_by_layer"].split()]
    assert here[0] == active[0] == 0 and here[1] == moe.args["pairs_here"]
    is_kernel = metric_reader("moe_roofline").is_kernel
    kernel_ns = tv.op_ns(is_kernel)
    assert kernel_ns > 0
    assert not any(is_kernel(n) for n in tv.op_names
                   if "kernel_metadata={}" in n)
    least = 0.0
    flops = nbytes = 0.0
    for k, n in ((2048, 1408), (2048, 1408), (1408, 2048)):
        flops += 2.0 * here[1] * k * n
        nbytes += active[1] * (k * n * 6 / 8 + 8) + 2.0 * here[1] * (k + n)
    least = least_time_s(flops, nbytes, "TPU v5 lite")
    view = _fixture_view(cell, tv)
    got = metric_reader("moe_roofline").read(view)
    assert got == pytest.approx(100.0 * least / (kernel_ns / 1e9))
    assert 0 < got <= 100
    runs = [(m.start, m.start + m.dur) for m in pv.modules
            if m.path and m.path[0] == "step"]
    want = tv.op_ns(is_kernel, runs) / 1e6 / 3
    assert metric_reader("expert_ms_per_step").read(view) == \
        pytest.approx(want) == 0.0
    assert sum(1 for n in tv.op_names if is_kernel(n)) == 3


def test_readers_read_nothing_without_the_kernel(cell, monkeypatch):
    """A program without the grouped kernel or the counters (the parent)
    gives no value."""
    pv = program_trace.ProgramView({}, [], (0, 1))
    monkeypatch.setattr(program_trace, "view_of", lambda v: pv)
    tv = trace.TraceView(np.zeros((0, 2), np.int64), np.array([], object),
                         np.zeros(0, np.int64), np.zeros(0, np.int64), {},
                         (0, 1), 1)
    view = types.SimpleNamespace(trace=tv, records=[],
                                 dims=cell.family.dims(cell.model),
                                 device_kind="TPU v5 lite",
                                 family=cell.family)
    for name in ("moe_roofline", "expert_ms_per_step"):
        assert metric_reader(name).read(view) is None
