"""The Mellum2 cell's files: its configuration loads, its counts read
the engine's ``moe.*`` counters, its three readers return numbers on a
recorded context, and the whole harness runs a configuration of its
kind (routed experts, sliding and full layers) at a size a CPU holds,
against the ``moe_swa_gqa`` reference."""
import json
import shutil
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import run, trace_spans  # noqa: E402

CELL = "mellum2.code.q4a8"
READERS = ("moe_mm_roofline", "moe_decode_share", "moe_expert_imbalance")
WORK = run._load_module(ROOT / "bench" / "work" / "moe_swa_gqa.py",
                        "work_moe_swa_gqa")


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL)


def test_config_loads_and_builds_the_model(cell):
    from repro.models.common import ModelConfig
    conf = cell["config"]
    cfg = ModelConfig(**conf["model"])
    assert cfg.layer_types == ("sliding", "sliding", "sliding", "full")
    assert cfg.lane_window is None
    assert conf["num_hidden_layers"] == cfg.n_layers == 8
    assert conf["layer_types"] == [
        "sliding_attention"] * 3 + ["full_attention"] + [
        "sliding_attention"] * 3 + ["full_attention"]
    # every width as published
    assert (conf["hidden_size"], conf["head_dim"], conf["num_experts"],
            conf["num_experts_per_tok"], conf["moe_intermediate_size"]) == (
        cfg.d_model, cfg.head_dim, cfg.n_experts, cfg.top_k, cfg.moe_ffn)
    assert conf["scopes"] == ["moe", "sail_mm", "attn"]
    assert run.counts_for(cell).__name__ == "counts_moe_swa_gqa"
    assert [m["name"] for m in cell["per_layer"]] == list(READERS)


def test_counts_take_top_k_experts_and_the_window(cell):
    m, prec = cell["config"]["model"], cell["traffic"]["precision"]
    d, f = m["d_model"], m["moe_ffn"]
    # 6 sliding layers reach 1024 positions, 2 full layers all 3000
    assert WORK.attended(m, 3000) == 6 * 1024 + 2 * 3000
    assert WORK.attended(m, 100) == 8 * 100
    f1, _ = WORK.decode_step(m, prec, [3000])
    f2, _ = WORK.decode_step(m, prec, [3000, 3000])
    # a lane: its projections and head, router and top-8 experts in
    # each layer, attention over what each layer reaches
    e, hd = m["n_experts"], m["d_head"]
    proj = 8 * (2 * d * 4096 + 2 * d * 512) + d * m["vocab"]
    assert f2 - f1 == (2 * proj + 8 * (2 * d * e + 8 * 6 * d * f)
                       + 4 * m["n_heads"] * hd * (6 * 1024 + 2 * 3000))
    routed = m["n_layers"] * m["top_k"] * 2 * 3 * d * f
    assert WORK.prefill_flops(m, 2048) > 2048 * routed


def test_decode_scopes_read_the_moe_counters(cell):
    m, prec = cell["config"]["model"], cell["traffic"]["precision"]
    steps = [[1500, 2000], [1501, 2001]]
    stats = {"moe.routed_pairs": 2 * 2 * 8 * 8,
             "moe.experts_touched": 2 * 8 * 15}
    got = WORK.decode_scopes(m, prec, steps, stats)
    d, f, e = m["d_model"], m["moe_ffn"], m["n_experts"]
    assert got["moe"][0] == 2 * d * e * 4 * 8 + 6 * d * f * 256
    assert got["moe"][1] == (4 * d * e * 8 * 2
                             + 240 * WORK.expert_bytes(m, prec))
    assert set(got) == {"moe", "sail_mm", "attn"}
    # without the counters (a program that keeps none) moe is left out
    assert set(WORK.decode_scopes(m, prec, steps, {})) == {"sail_mm", "attn"}


def _ctx():
    """A traced run's context as ``bench/run.py`` builds it, recorded
    numbers."""
    return {"trace": {"scope_s": {"moe": 1.2, "sail_mm": 0.3, "attn": 0.45,
                                  "other": 0.05}},
            "scope_work": {"moe": (6.0e12, 1.2e11), "sail_mm": (1, 1)},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "stats": {"moe.layer_steps": 800, "moe.imbalance_sum": 1640.0}}


def test_readers_return_numbers_on_a_recorded_context():
    got = {name: run._load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                                  name).read(_ctx()) for name in READERS}
    assert got["moe_mm_roofline"] == pytest.approx(
        1.2e11 / 819e9 / 1.2 * 100)
    assert got["moe_decode_share"] == pytest.approx(60.0)
    assert got["moe_expert_imbalance"] == pytest.approx(2.05)
    # a program without the scope or the counters: nothing to read
    bare = {"trace": {"scope_s": {"sail_mm": 1.0}}, "stats": {},
            "scope_work": None, "peaks": _ctx()["peaks"]}
    assert all(run._load_module(ROOT / "bench" / "metrics" / f"{n}.py",
                                n).read(bare) is None for n in READERS)


SMOKE_MODEL = {
    "name": "mellum-smoke", "family": "moe", "vocab": 256, "d_model": 128,
    "n_layers": 4, "n_heads": 4, "n_kv": 2, "d_head": 32, "d_ff": 256,
    "norm_eps": 1e-6, "rope_theta": 500000.0, "window": 8,
    "layer_types": ["sliding", "sliding", "sliding", "full"],
    "yarn_factor": 4.0, "yarn_original_max": 16,
    "n_experts": 8, "top_k": 2, "moe_ffn": 128, "attn_chunk": 8,
    "max_seq": 512}


def _hand_trace():
    """``trace_spans.json``'s trace, its matmul ops in the ``moe``
    scope."""
    raw = json.loads((HERE / "data" / "trace_spans.json").read_text())
    raw = json.loads(json.dumps(raw).replace("/sail_mm/", "/moe/"))
    return {"device": {p: {k: [tuple(e) for e in v] for k, v in lines.items()}
                       for p, lines in raw["device"].items()},
            "host": [tuple(e) for e in raw["host"]],
            "op_scopes": raw["op_scopes"]}


def test_harness_runs_a_routed_sliding_full_model(tmp_path, tmp_path_factory,
                                                  monkeypatch):
    from repro.models.common import ModelConfig
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for d in bench["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    conf = json.loads((ROOT / "bench" / "configs"
                       / "mellum2_12b_a2_5b.json").read_text())
    model = dict(conf["model"], **SMOKE_MODEL)
    ModelConfig(**model)
    conf.update(name="mellum_smoke", model=model)
    (tmp_path / "bench" / "configs" / "mellum_smoke.json").write_text(
        json.dumps(conf))
    bench["configs"].append({"name": "mellum_smoke", "source": "test",
                             "file": "bench/configs/mellum_smoke.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "mellum.smoke", "chips": 1,
                               "config": "mellum_smoke",
                               "traffic": "mellum_smoke", "why": "test"})
    tr = json.loads((HERE / "data" / "smoke.q4a8.json").read_text())
    tr["engine"]["cache_len"] = 40        # prompts to 24, outputs to 12
    (tmp_path / "bench" / "traffic" / "mellum_smoke.json").write_text(
        json.dumps(tr))
    for spec in bench["per_layer"]:
        if spec["name"] in READERS:
            spec["workloads"] = ["mellum.smoke"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(run, "check_device", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path_factory.mktemp("trace"))
    monkeypatch.setattr(trace_spans, "load", lambda path: _hand_trace())
    c = run.load_cell("mellum.smoke", root=tmp_path)
    res = run.run_cell(c, 2**33 + 3, 1.5, True, t_process=time.perf_counter())
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(got) == set(READERS), res["metrics"]
    assert 0 < got["moe_decode_share"] < 100
    assert 0 < got["moe_mm_roofline"]
    # no expert takes more than every lane's token
    lanes = tr["engine"]["batch_size"]
    assert 1.0 <= got["moe_expert_imbalance"] <= lanes
    assert res["correct"], res["check"]
    assert res["check"]["max_logit_gap"]["value"] < 1e-3


def test_make_params_frees_codes_an_earlier_copy_kept(cell):
    """``bench/run.py`` loads the reference anew for each use; the codes
    a copy kept for one seed's check are freed when the next seed's
    weights are made, so seeds can follow one another in one process
    (``bench/control.py``)."""
    import gc
    import weakref
    path = ROOT / "bench" / "reference" / "moe_swa_gqa.py"
    m = dict(cell["config"]["model"], **SMOKE_MODEL)
    prec = dict(cell["traffic"]["precision"], group_size=32)
    first = run._load_module(path, "reference")
    params = first.make_params(m, 5)
    first.logits(params, list(range(12)), 6, m, prec)
    codes = first._KEPT[next(iter(first._KEPT))][1][0]["w_up"][0]
    kept = weakref.ref(codes)
    del first, params, codes
    gc.disable()                  # only make_params may collect
    try:
        run._load_module(path, "reference").make_params(m, 6)
    finally:
        gc.enable()
    assert kept() is None
