"""The reduction from a profiler trace to busy time, module time and
labelled idle gaps, on a small trace written by hand."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench import trace_reduce  # noqa: E402


def _read(name):
    raw = json.loads((HERE / "data" / f"{name}.json").read_text())
    return {"device": {p: {k: [tuple(e) for e in v] for k, v in lines.items()}
                       for p, lines in raw["device"].items()},
            "host": [tuple(e) for e in raw["host"]]}


@pytest.fixture
def small():
    return _read("trace_small")


def test_window_is_the_generate_span(small):
    assert trace_reduce.window(small["host"], "bench.generate") == (0, 1000)
    assert trace_reduce.window(small["host"], "nothing") is None


def test_busy_is_the_union_of_ops_inside_the_window(small):
    r = trace_reduce.reduce(small, 0, 1000)
    # [0,30) early op clipped, [100,250) two overlapping fusions,
    # [400,450), [900,1000) clipped at the window's end
    assert r["busy_s"] == pytest.approx((30 + 150 + 50 + 100) / 1e9)
    assert r["window_s"] == pytest.approx(1000 / 1e9)


def test_modules_are_summed_by_name_without_their_id(small):
    r = trace_reduce.reduce(small, 0, 1000)
    assert r["modules"] == {"jit_decode_step": pytest.approx(350 / 1e9),
                            "jit_scan": pytest.approx(50 / 1e9)}
    assert r["device_ops"][0][0] == "jit_decode_step"


def test_idle_gaps_are_longest_first_and_labelled_by_host_span(small):
    r = trace_reduce.reduce(small, 0, 1000)
    (l1, s1), (l2, s2), (l3, s3) = r["idle_gaps"]
    assert (l1, s1) == ("bench.wait_arrival", pytest.approx(450 / 1e9))
    assert l2 == "bench.step > PjitFunction(decode_step)"
    assert s2 == pytest.approx(150 / 1e9)
    assert (l3, s3) == ("bench.step", pytest.approx(70 / 1e9))


def test_a_plane_that_runs_nothing_counts_no_busy_time(small):
    # a v5e trace holds /device:TPU:0 and an empty
    # /device:CUSTOM:Megascale Trace; busy time averaged over both read
    # half the chip's
    two = _read("trace_two_planes")
    assert set(two["device"]) == {"/device:TPU:0",
                                  "/device:CUSTOM:Megascale Trace"}
    r = trace_reduce.reduce(two, 0, 1000)
    assert r["busy_s"] == pytest.approx((30 + 150 + 50 + 100) / 1e9)
    # module time and the idle gaps are those of the one plane alone
    assert r == trace_reduce.reduce(small, 0, 1000)
    assert [g[0] for g in r["idle_gaps"]] == [
        "bench.wait_arrival", "bench.step > PjitFunction(decode_step)",
        "bench.step"]


def test_load_keeps_only_device_planes_that_run_ops():
    ev = lambda n, s, d: SimpleNamespace(name=n, start_ns=s, duration_ns=d)
    line = lambda n, *es: SimpleNamespace(name=n, events=list(es))
    plane = lambda n, *ls: SimpleNamespace(name=n, lines=list(ls))
    device, host = trace_reduce.read_planes([
        plane("/device:TPU:0", line("XLA Ops", ev("fusion", 5, 2)),
              line("XLA Modules", ev("jit_f(1)", 4, 4))),
        plane("/device:TPU:1", line("XLA Modules", ev("jit_f(1)", 4, 4))),
        plane("/device:CUSTOM:Megascale Trace"),
        plane("/device:TPU:2", line("XLA Ops")),
        plane("/host:CPU", line("python", ev("bench.generate", 0, 9))),
        plane("/device:CPU:0", line("XLA Ops", ev("fusion", 5, 2)))])
    assert set(device) == {"/device:TPU:0", "/device:TPU:1"}
    assert device["/device:TPU:0"] == {"ops": [("fusion", 5, 2)],
                                       "modules": [("jit_f(1)", 4, 4)],
                                       "sync_ops": [("fusion", 5, 2)]}
    assert host == [("bench.generate", 0, 9)]


def test_a_trace_without_a_device_is_refused():
    with pytest.raises(ValueError, match="no device plane"):
        trace_reduce.reduce({"device": {}, "host": []}, 0, 1)


def test_load_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.generate"):
        jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    got = trace_reduce.load(str(path))
    win = trace_reduce.window(got["host"], "bench.generate")
    assert win is not None and win[1] > win[0]
    assert got["device"] == {}      # the host CPU is no device plane
