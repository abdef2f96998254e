"""BENCHMARK.json against its own files: every cell, configuration,
traffic mix and per-layer reader is found by name, and a new mix is
picked up from a new file and a new ``workloads`` entry alone."""
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run, traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [w["traffic"] for w in BENCH["workloads"]])
    assert all(NAME.match(n) for n in names), names
    assert len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s"}
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_with_its_config_traffic_and_reference(cell):
    c = run.load_cell(cell)
    assert c["workload"]["chips"] == 1
    assert (ROOT / "bench" / "reference"
            / f"{c['config']['reference']}.py").is_file()
    assert set(c["config"]["reduced"]) == set(next(
        x for x in BENCH["configs"]
        if x["name"] == c["workload"]["config"])["reduced"])
    tr = c["traffic"]
    assert tr["plan"] == "none" or tr["plan"].startswith("uniform:")
    assert set(tr["engine"]) == set(run.ENGINE_KEYS)
    assert tr["precision"]["kv_bits"] in (8, 32)
    assert 0 < tr["check"]["max_logit_gap"]
    assert c["per_layer"] and c["end_to_end"]


@pytest.mark.parametrize("spec", BENCH["per_layer"], ids=lambda s: s["name"])
def test_per_layer_reader_declares_what_benchmark_json_says(spec):
    mod = run._load_module(ROOT / "bench" / "metrics" / f"{spec['name']}.py",
                           spec["name"])
    assert (mod.UNIT, mod.LAYER, mod.SOURCE, mod.MOVES) == (
        spec["unit"], spec["layer"], spec["source"], spec["moves"])
    assert spec["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    assert set(spec["workloads"]) <= set(CELLS)


def test_a_new_traffic_file_and_workload_entry_are_found_by_name(tmp_path):
    for p in ("bench/configs", "bench/traffic", "bench/metrics",
              "bench/reference"):
        shutil.copytree(ROOT / p, tmp_path / p)
    shutil.copy(ROOT / "bench" / "peaks.json", tmp_path / "bench")
    dummy = json.loads((ROOT / "bench/traffic/decode.q4a8.json").read_text())
    dummy.update(loop="open", rate_req_s=1.5)
    (tmp_path / "bench/traffic/dummy.json").write_text(json.dumps(dummy))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "tinymistral.dummy", "chips": 1,
                               "config": "tinymistral_248m",
                               "traffic": "dummy", "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    c = run.load_cell("tinymistral.dummy", root=tmp_path)
    assert c["traffic"]["rate_req_s"] == 1.5
    assert c["config"]["name"] == "tinymistral_248m"
    # metrics that list their cells do not reach the new one
    assert c["per_layer"] == []
    assert [m["name"] for m in c["end_to_end"]] == [
        m["name"] for m in BENCH["end_to_end"] if "workloads" not in m]


def test_peak_table_refuses_an_unknown_device_kind():
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(run.Unfit, match="no peaks"):
        run.peaks_for("TPU v99")


def test_every_mix_validates():
    for p in (ROOT / "bench" / "traffic").glob("*.json"):
        traffic.validate(json.loads(p.read_text()))
