"""The correctness comparison, at a size a CPU test run can hold.

The control (the reference one precision step below the cell's: 3-bit
weights for a 4-bit plan) has to read above the limit while the program
reads below it; and a run whose timed path is broken underneath has to
come out not correct, for each fault a served cell can have: a token
altered where it is produced, and a decode step that returns its state
unchanged.  The harness's look for a chip is skipped; the rest of a run
is driven as ``bench/run.py`` drives it.
"""
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import run  # noqa: E402

SEED = 2**33 + 7        # wider than 32 bits, as benchmark seeds may be


@pytest.fixture
def cell(monkeypatch):
    monkeypatch.setattr(run, "check_device", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return {"workload": {"name": "smoke", "chips": 1},
            "config": json.loads((HERE / "data/smoke_config.json").read_text()),
            "traffic": json.loads((HERE / "data/smoke.q4a8.json").read_text()),
            "root": ROOT, "end_to_end": bench["end_to_end"], "per_layer": []}


def _run(cell, control=False):
    return run.run_cell(cell, SEED, 1.5, False, control=control,
                        t_process=time.perf_counter())


def test_program_passes_and_control_fails(cell):
    res = _run(cell, control=True)
    c = res["check"]
    assert res["correct"], c
    assert c["tokens_checked"] >= 10
    assert c["control_max_logit_gap"]["value"] > c["max_logit_gap"]["limit"]
    assert set(res["metrics"]) >= {"output_tok_s", "setup_s"}
    assert list(res)[-1] == "check"


def test_altered_token_is_not_correct(cell, monkeypatch):
    from repro.serving.engine import Engine
    sample = Engine._sample
    calls = {"n": 0}

    def altered(self, logits, uids=None, indices=None):
        out = np.array(sample(self, logits, uids, indices))
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            out = (out + 1) % logits.shape[-1]
        return out

    monkeypatch.setattr(Engine, "_sample", altered)
    assert not _run(cell)["correct"]


def test_decode_step_that_keeps_its_state_is_not_correct(cell, monkeypatch):
    from repro.models import lm
    step = lm.decode_step

    def frozen(params, tokens, cache, *a, **kw):
        logits, _ = step(params, tokens, cache, *a, **kw)
        return logits, cache

    monkeypatch.setattr(lm, "decode_step", frozen)
    assert not _run(cell)["correct"]
