"""Trace-driven workload generation: determinism (same seed => identical
trace), JSON round-trips, arrival-process invariants, length-distribution
bounds, and the serve_bench workload builder riding on the generator."""

import json
import os
import sys

import numpy as np
import pytest

from repro.serving.workload import (
    ArrivalSpec,
    LengthDist,
    Trace,
    TraceSpec,
    generate,
    spec_for_ratio,
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def bursty_spec(seed=7, n=10):
    return TraceSpec(
        seed=seed,
        n_requests=n,
        vocab=97,
        prompt=LengthDist("uniform", low=4, high=12),
        output=LengthDist("constant", low=8, high=8),
        arrival=ArrivalSpec("bursty", gap=2.0, burst=5),
    )


def test_generate_deterministic():
    """The reproducibility contract: same spec => bit-identical trace."""
    a, b = generate(bursty_spec()), generate(bursty_spec())
    assert a.requests == b.requests
    assert a.trace_hash == b.trace_hash
    c = generate(bursty_spec(seed=8))
    assert c.requests != a.requests
    assert c.trace_hash != a.trace_hash


def test_trace_json_round_trip(tmp_path):
    tr = generate(bursty_spec())
    again = Trace.from_json(tr.to_json())
    assert again == tr
    assert again.trace_hash == tr.trace_hash
    path = tmp_path / "trace.json"
    tr.save(str(path))
    loaded = Trace.load(str(path))
    assert loaded == tr
    # the stored requests ARE the replay source (generator evolution
    # cannot silently change a saved trace)
    blob = json.loads(path.read_text())
    assert len(blob["requests"]) == tr.spec.n_requests
    assert blob["version"] == 1


def test_trace_version_gate():
    with pytest.raises(ValueError, match="version"):
        Trace.from_json({"version": 99, "spec": {}, "requests": []})


@pytest.mark.parametrize("process", ["fixed", "poisson", "bursty", "diurnal"])
def test_arrival_invariants(process):
    """Every process yields n nondecreasing iteration indices from 0."""
    spec = ArrivalSpec(process=process, gap=3.0, burst=4)
    rng = np.random.default_rng(3)
    arr = spec.arrival_iterations(rng, 24)
    assert arr.shape == (24,)
    assert arr.dtype == np.int64
    assert arr[0] == 0
    assert (np.diff(arr) >= 0).all()


def test_fixed_arrivals_are_exact():
    arr = ArrivalSpec("fixed", gap=3.0).arrival_iterations(np.random.default_rng(0), 5)
    assert arr.tolist() == [0, 3, 6, 9, 12]


def test_bursty_arrivals_come_in_bursts():
    spec = ArrivalSpec("bursty", gap=2.0, burst=5)
    arr = spec.arrival_iterations(np.random.default_rng(7), 10)
    # requests land in groups of `burst` simultaneous arrivals
    assert (arr[:5] == arr[0]).all()
    assert (arr[5:] == arr[5]).all()
    assert arr[5] > arr[0]


def test_length_dist_bounds():
    rng = np.random.default_rng(0)
    uni = LengthDist("uniform", low=3, high=9).sample(rng, 200)
    assert uni.min() >= 3 and uni.max() <= 9
    log = LengthDist("lognormal", low=2, high=40, mean=8.0, sigma=1.0).sample(rng, 200)
    assert log.min() >= 2 and log.max() <= 40
    const = LengthDist("constant", low=6, high=6).sample(rng, 5)
    assert (const == 6).all()


def test_length_dist_validation():
    with pytest.raises(ValueError, match="kind"):
        LengthDist("zipf")
    with pytest.raises(ValueError, match="low"):
        LengthDist("uniform", low=0)
    with pytest.raises(ValueError, match="< low"):
        LengthDist("uniform", low=5, high=4)
    with pytest.raises(ValueError, match="process"):
        ArrivalSpec("weekly")
    with pytest.raises(ValueError, match="amplitude"):
        ArrivalSpec("diurnal", amplitude=1.5)


def test_spec_for_ratio():
    spec = spec_for_ratio(2.0, n_requests=8, output_tokens=10)
    assert spec.output.expected == 10
    assert spec.prompt.expected == pytest.approx(20, rel=0.3)
    assert spec.prefill_decode_ratio == pytest.approx(2.0, rel=0.3)
    tr = generate(spec)
    assert len(tr.requests) == 8
    assert all(r.max_new_tokens == 10 for r in tr.requests)
    with pytest.raises(ValueError, match="positive"):
        spec_for_ratio(-1.0)


def test_prompt_tokens_in_vocab():
    tr = generate(bursty_spec())
    for r in tr.requests:
        assert all(0 <= t < 97 for t in r.prompt)
        assert len(r.prompt) >= 4


def test_build_workload_reproducible():
    """serve_bench's workload is a Trace, reproducible from (seed, spec),
    with the arrival process selectable by name."""
    from benchmarks.serve_bench import build_workload

    class _Cfg:
        vocab = 128

    a = build_workload(_Cfg, 6, 12, 3, seed=5, arrival="poisson")
    b = build_workload(_Cfg, 6, 12, 3, seed=5, arrival="poisson")
    assert a.requests == b.requests and a.trace_hash == b.trace_hash
    assert a.spec.arrival.process == "poisson"
    c = build_workload(_Cfg, 6, 12, 3, seed=6, arrival="poisson")
    assert c.requests != a.requests


def test_serve_bench_requires_a_gate(monkeypatch, capsys):
    """serve_bench has no default mode: without a gate flag it stops with
    argparse's usage error, which names the gates, before any model is
    built."""
    import benchmarks.serve_bench as sb

    monkeypatch.setattr(sb, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", ["serve_bench.py", "--requests", "2"])
    with pytest.raises(SystemExit) as exc:
        sb.main()
    assert exc.value.code == 2
    err = capsys.readouterr().err
    for flag in ("--replay", "--paged-gate", "--speculative"):
        assert flag in err
