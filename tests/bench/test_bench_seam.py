"""A new configuration brings its own work counts, scope list and program
counters to the harness with new files and ``BENCHMARK.json`` entries
alone.

The test writes, into a copy of the benchmark's files, a configuration
that names its own counts module and scopes (as one with routed experts
would), the counts module, and readers of what it brings, then drives a
traced run of ``bench/run.py`` unchanged at a size a CPU holds.  The
engine's ``stats()`` gains a counter the harness has never heard of.  On
the CPU the profiler records no device, so the trace read is a small
trace written by hand whose ops carry the new configuration's scope.
"""
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

SEED = 2**33 + 11
CELL = "seam.smoke"
TOP_K = 6            # experts a token is routed to, as a counter reports

COUNTS = '''
from bench import counts

def decode_step(m, prec, contexts):
    f, b = counts.decode_step(m, prec, contexts)
    return 2 * f, 2 * b

def prefill_flops(m, prompt_len):
    return 7 * prompt_len

def decode_scopes(m, prec, steps, stats):
    return {"experts": (0, 1000 * stats["moe.experts_touched"]),
            "attn": (len(steps), 0)}
'''

# name: body of read(ctx)
READERS = {
    # the engine's new counter, per decode step of the window
    "seam_experts_per_step": 'return (ctx["stats"]["moe.experts_touched"]'
                             ' / ctx["counters"]["decode_iterations"])',
    # the counts module saw the counter: 1000 bytes per expert touched
    "seam_bytes_per_expert": 'return (ctx["scope_work"]["experts"][1]'
                             ' / ctx["stats"]["moe.experts_touched"])',
    # the configuration's scope, in nanoseconds of op time
    "seam_experts_ns": 'return ctx["trace"]["scope_s"]["experts"] * 1e9',
    # the configuration's counts over the model, precision and contexts
    # the harness hands on: twice the dense counts
    "seam_flops_ratio": ('from bench import counts\n'
                         '    dense = sum(counts.decode_step(ctx["model"], '
                         'ctx["precision"], c)[0] for c in ctx["contexts"])\n'
                         '    return ctx["decode_flops"] / dense'),
    "seam_experts_roofline": ('from bench.roofline import scope_share\n'
                              '    return scope_share(ctx, "experts")'),
}
# readers already in the benchmark that read spans, queue and idle time
SHARED = ("prefill_span_ms_per_ktok", "host_jit_share", "queue_wait_ms",
          "idle_host_work_share", "idle_device_wait_share")


def _hand_trace():
    """``trace_spans.json``'s trace with its ``sail_mm`` ops in an
    ``experts`` scope."""
    raw = json.loads((HERE / "data" / "trace_spans.json").read_text())
    raw = json.loads(json.dumps(raw).replace("/sail_mm/", "/experts/"))
    return {"device": {p: {k: [tuple(e) for e in v] for k, v in lines.items()}
                       for p, lines in raw["device"].items()},
            "host": [tuple(e) for e in raw["host"]],
            "op_scopes": raw["op_scopes"]}


@pytest.fixture
def seam_root(tmp_path):
    """A copy of the benchmark's files plus the new configuration's."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for d in bench["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = {p.relative_to(tmp_path): p.read_bytes()
              for p in (tmp_path / "bench").rglob("*.py")}
    b = tmp_path / "bench"
    conf = json.loads((HERE / "data" / "smoke_config.json").read_text())
    conf.update(name="seam_moe", counts="seam_moe",
                scopes=["experts", "attn"])
    (b / "configs" / "seam_moe.json").write_text(json.dumps(conf))
    (b / "work").mkdir(exist_ok=True)
    (b / "work" / "seam_moe.py").write_text(COUNTS)
    shutil.copy(HERE / "data" / "smoke.q4a8.json",
                b / "traffic" / "seam.q4a8.json")
    for name, body in READERS.items():
        (b / "metrics" / f"{name}.py").write_text(
            f"def read(ctx):\n    {body}\n")
    bench["configs"].append({"name": "seam_moe", "source": "test",
                             "file": "bench/configs/seam_moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "seam_moe",
                               "traffic": "seam.q4a8", "chips": 1,
                               "why": "test"})
    for name in READERS:
        bench["per_layer"].append({
            "name": name, "unit": "x", "better": "higher",
            "source": "program_counter", "layer": "test",
            "moves": "output_tok_s", "workloads": [CELL]})
    for spec in bench["per_layer"]:
        if spec["name"] in SHARED:
            spec["workloads"].append(CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    yield tmp_path
    # no file of the harness was edited
    assert all((tmp_path / p).read_bytes() == v for p, v in before.items())


def test_new_counts_scopes_and_counters_reach_their_readers(
        seam_root, tmp_path_factory, monkeypatch):
    from repro.serving.engine import Engine
    stats = Engine.stats

    def with_experts(self):
        out = stats(self)
        out["moe"] = {"experts_touched": TOP_K * self.decode_iterations,
                      "routed": True}
        return out

    monkeypatch.setattr(Engine, "stats", with_experts)
    monkeypatch.setattr(run, "check_device", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path_factory.mktemp("trace"))
    monkeypatch.setattr(trace_spans, "load", lambda path: _hand_trace())
    cell = run.load_cell(CELL, root=seam_root)
    res = run.run_cell(cell, SEED, 1.5, True, t_process=time.perf_counter())
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(got) == set(READERS) | set(SHARED), res["metrics"]
    assert got["seam_experts_per_step"] == TOP_K
    assert got["seam_bytes_per_expert"] == 1000
    assert got["seam_experts_ns"] == pytest.approx(200)
    assert got["seam_flops_ratio"] == 2
    assert 0 < got["seam_experts_roofline"]
    assert res["correct"], res["check"]
