"""The program's spans and scopes in a trace (``bench/trace_spans.py``)
and the eight readers of them, on small traces written by hand."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))

from bench import run, trace_reduce, trace_spans  # noqa: E402


def _read(name):
    raw = json.loads((HERE / "data" / f"{name}.json").read_text())
    return {"device": {p: {k: [tuple(e) for e in v] for k, v in lines.items()}
                       for p, lines in raw["device"].items()},
            "host": [tuple(e) for e in raw["host"]],
            "op_scopes": raw.get("op_scopes", {})}


@pytest.fixture
def spans():
    return _read("trace_spans")


@pytest.fixture
def small():
    return _read("trace_small")


def test_idle_is_put_down_to_the_innermost_engine_span(spans):
    got = trace_spans.idle_by_span(spans, 0, 1000)
    # gaps [0,100) [300,500) [540,700) [830,1000); see the host spans
    want = {"engine.admit": 20, "engine.prefill": 70 + 50 + 60 + 5,
            "engine.decode": 10 + 10 + 10 + 10,
            "engine.sample": 30 + 45 + 40, "engine.step": 10 + 20,
            "engine.commit": 30, "engine.kv_grant": 10, "none": 100 + 100}
    assert got == pytest.approx({k: v / 1e9 for k, v in want.items()})
    r = trace_reduce.reduce(spans, 0, 1000)
    assert sum(got.values()) == pytest.approx(r["window_s"] - r["busy_s"])


SCOPES = json.loads((ROOT / "bench/configs/tinymistral_248m.json")
                    .read_text())["scopes"]


def test_decode_step_op_time_is_split_by_scope(spans):
    got = trace_spans.scope_s(spans, 0, 1000, SCOPES)
    # the jit_scan op of a sail_mm scope lies outside decode_step; the
    # while op counts its own 10 ns beside copy.3's 30
    assert got == pytest.approx({"sail_mm": 200e-9, "attn": 90e-9,
                                 "other": 40e-9})
    assert trace_spans.scope_of("a/attn_norm/mul:", SCOPES) == "other"
    ops = spans["device"]["/device:TPU:0"]["sync_ops"]
    assert trace_spans.self_times(ops)[:4] == [10, 100, 60, 30]


@pytest.mark.parametrize("scopes, want", [
    (SCOPES, {"sail_mm": 200, "attn": 90, "other": 40}),
    (["attn"], {"attn": 90, "other": 240}),
    (["attn", "body"], {"attn": 90, "body": 230, "other": 10}),
    ([], {"other": 330}),
], ids=["config", "attn", "first_listed", "none"])
def test_scope_list_is_the_configurations(spans, scopes, want):
    # an op counts for the first listed scope on its path, else other
    got = trace_spans.scope_s(spans, 0, 1000, scopes)
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert sum(got.values()) == pytest.approx(330e-9)


def test_a_trace_without_program_spans_reads_only_none(small):
    r = trace_reduce.reduce(small, 0, 1000)
    got = trace_spans.reduce(small, 0, 1000, SCOPES)
    assert got["idle_by_span"] == pytest.approx(
        {"none": r["window_s"] - r["busy_s"]})
    assert got["scope_s"] == {}
    # trace_reduce's numbers of the same trace are untouched
    assert r["busy_s"] == pytest.approx(330e-9)
    assert [g[0] for g in r["idle_gaps"]] == [
        "bench.wait_arrival", "bench.step > PjitFunction(decode_step)",
        "bench.step"]


def _pb(field, value):
    """One protobuf field: an int as a varint, bytes/str length-delimited."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    if isinstance(value, int):
        return varint(field << 3) + varint(value % 2 ** 64)
    value = value.encode() if isinstance(value, str) else value
    return varint(field << 3 | 2) + varint(len(value)) + value


def test_op_scopes_are_read_from_the_event_metadata():
    def stat(meta, field, value):
        return _pb(5, _pb(1, meta) + _pb(field, value))
    stat_meta = b"".join(_pb(5, _pb(1, k) + _pb(2, _pb(1, k) + _pb(2, n)))
                         for k, n in ((1, "tf_op"), (2, "program_id"),
                                      (3, "jit(f)/attn/x:")))
    ops = (_pb(4, _pb(1, 10) + _pb(2, _pb(1, 10) + _pb(2, "%fusion.1 = f")
                                   + stat(1, 5, "jit(g)/sail_mm/dot:")
                                   + stat(2, 3, 2 ** 63 + 5)))
           + _pb(4, _pb(1, 11) + _pb(2, _pb(2, "%copy.2 = c")
                                     + stat(1, 7, 3) + stat(2, 4, -5)))
           + _pb(4, _pb(1, 12) + _pb(2, _pb(2, "no scope"))))
    lines = _pb(3, _pb(2, "XLA Ops") + _pb(4, _pb(1, 10) + _pb(3, 7)))
    xspace = (_pb(1, _pb(2, "/device:TPU:0") + lines + ops + stat_meta)
              + _pb(1, _pb(2, "/host:CPU") + stat_meta + ops))
    assert trace_spans.op_scopes(xspace) == {"/device:TPU:0": [
        [2 ** 63 + 5, "%fusion.1 = f", "jit(g)/sail_mm/dot:"],
        [2 ** 64 - 5, "%copy.2 = c", "jit(f)/attn/x:"]]}


def test_load_reads_engine_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    from repro.serving.trace import SpanTable
    spans = SpanTable()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with spans.span("engine.step"):
        with spans.span("engine.sample", lanes=3):
            jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    got = trace_spans.load(str(next(tmp_path.rglob("*.xplane.pb"))))
    names = [n for n, _, _ in got["host"]]
    assert "engine.step" in names and "engine.sample" in names
    assert got["device"] == {} and got["op_scopes"] == {}
    assert [p[2] for p in trace_spans.innermost(got["host"])] == [
        "engine.step", "engine.sample", "engine.step"]


def test_span_deltas_over_a_window():
    from repro.serving import trace
    eng = SimpleNamespace(spans=trace.SpanTable(), queue_wait_seconds=0.5,
                          queue_admissions=2)
    with eng.spans.span("engine.step"):
        pass
    before = trace_spans.snapshot(eng)
    for _ in range(3):
        with eng.spans.span("engine.step"):
            with eng.spans.span("engine.decode"):
                pass
    eng.queue_wait_seconds, eng.queue_admissions = 0.75, 4
    got = trace_spans.delta(before, trace_spans.snapshot(eng))
    assert set(got["spans"]) <= {"engine.step", "engine.decode", "none"}
    assert got["spans"]["engine.step"]["count"] == 3
    assert got["spans"]["engine.decode"]["count"] == 3
    assert got["queue_wait_s"] == pytest.approx(0.25)
    assert got["queue_admissions"] == 2
    assert trace_spans.snapshot(SimpleNamespace()) is None
    assert trace_spans.delta(None, None) is None


def test_stats_numbers_by_dotted_path_and_their_window_change():
    import numpy as np
    before = {"decode_iterations": 10, "kv_bits": 8, "drift": None,
              "mixed_precision": False, "plan_mode": "uniform",
              "block_pool": {"free_blocks": np.int64(40), "util": 0.5}}
    after = dict(before, decode_iterations=25,
                 block_pool={"free_blocks": np.int64(31), "util": 0.75},
                 moe={"experts_touched": 90})
    leaves = trace_spans.numeric_leaves(after)
    assert leaves == {"decode_iterations": 25, "kv_bits": 8,
                      "block_pool.free_blocks": 31, "block_pool.util": 0.75,
                      "moe.experts_touched": 90}
    assert trace_spans.leaves_delta(trace_spans.numeric_leaves(before),
                                    leaves) == {
        "decode_iterations": 15, "kv_bits": 0, "block_pool.free_blocks": -9,
        "block_pool.util": 0.25, "moe.experts_touched": 90}


def _ctx(**kw):
    ctx = {"window_s": 30.0, "counters": {"prefill_tokens": 4000},
           "trace": {"window_s": 30.0,
                     "idle_by_span": {"engine.sample": 12.0,
                                      "engine.prefill": 4.5,
                                      "engine.commit": 0.3, "none": 0.6},
                     "scope_s": {"sail_mm": 9.0, "attn": 2.0,
                                 "other": 1.0}},
           "spans": {"engine.prefill": {"count": 9, "s": 6.0, "self_s": 1.0,
                                        "jit_s": 4.8, "jit_events": 27},
                     "engine.decode": {"count": 300, "s": 20.0,
                                       "self_s": 10.0, "jit_s": 0.0,
                                       "jit_events": 0},
                     "none": {"count": 0, "s": 0.0, "self_s": 0.0,
                              "jit_s": 0.3, "jit_events": 1}},
           "queue": {"wait_s": 0.06, "admissions": 20},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "scope_work": {"sail_mm": (1e9, 819e9 * 0.09),
                          "attn": (197e12 * 0.5, 1e6)}}
    ctx.update(kw)
    return ctx


READINGS = {
    "prefill_span_ms_per_ktok": (6.0 / 4000 * 1e6,
                                 dict(counters={"prefill_tokens": 0})),
    "host_jit_share": ((4.8 + 0.3) / 30.0 * 100, dict(spans=None)),
    "queue_wait_ms": (3.0, dict(queue={"wait_s": 0.0, "admissions": 0})),
    "idle_host_work_share": ((4.5 + 0.3) / 30.0 * 100,
                             dict(trace={"window_s": 30.0,
                                         "idle_by_span": {"none": 9.0}})),
    "idle_device_wait_share": (40.0, dict(trace=None)),
    "decode_matmul_share": (75.0, dict(trace={"window_s": 30.0,
                                              "scope_s": {"other": 5.0}})),
    # bound by bytes: 0.09 s of HBM time over 9 s in sail_mm
    "lut_mm_roofline": (1.0, dict(scope_work=None)),
    # bound by FLOPs: 0.5 s at the bf16 peak over 2 s in attn
    "decode_attn_roofline": (25.0, dict(scope_work={"attn": (0, 0)})),
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_reader_gives_its_value_and_none_without_its_input(name):
    mod = run._load_module(ROOT / "bench" / "metrics" / f"{name}.py", name)
    want, missing = READINGS[name]
    assert mod.read(_ctx()) == pytest.approx(want)
    assert mod.read(_ctx(**missing)) is None
    # a trace without program spans or scopes, an engine without a
    # span table
    bare = _ctx(trace={"window_s": 30.0, "busy_s": 12.0})
    for k in ("spans", "queue"):
        del bare[k]
    assert mod.read(bare) is None
