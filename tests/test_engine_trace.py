"""Host spans and counters of ``Engine.step`` (``repro.serving.trace``):
which spans a step records and how they nest, the decode seconds they
carry, the queue-wait counters, JIT attribution, and the named scopes
that leave the compiled decode step unchanged."""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

import repro.configs as C
from repro.models import lm
from repro.serving import trace
from repro.serving.engine import Engine, EngineConfig

STEP_SPANS = ("engine.step", "engine.admit", "engine.prefill",
              "engine.commit", "engine.kv_grant", "engine.decode",
              "engine.sample")


@pytest.fixture(scope="module")
def tiny():
    cfg = C.get_smoke("tinymistral_248m")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_engine(tiny, **kw):
    cfg, params = tiny
    return Engine(params, cfg, EngineConfig(
        batch_size=4, cache_len=64, quantize=True, ql=4, group_size=32,
        quant_kv=True, kv_block_size=8, **kw))


class Recorder:
    """Stands in for ``TraceAnnotation``: logs each span's parent."""
    log = []

    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        Recorder.log.append(("enter", self.name, self.attrs))

    def __exit__(self, *exc):
        Recorder.log.append(("exit", self.name, None))


def parents(log):
    out, stack = [], []
    for kind, name, attrs in log:
        if kind == "enter":
            out.append((name, stack[-1] if stack else None, attrs))
            stack.append(name)
        else:
            assert stack.pop() == name
    assert not stack
    return out


def test_one_step_records_every_span_nested_as_the_code(tiny, monkeypatch):
    eng = make_engine(tiny)
    uids = [eng.submit([5, 9, 2, 4, 11], 4), eng.submit([1, 2, 3], 4)]
    monkeypatch.setattr(trace, "TraceAnnotation", Recorder)
    Recorder.log = []
    eng.step()
    got = parents(Recorder.log)
    assert {n for n, _, _ in got} == set(STEP_SPANS)
    assert got[0][:2] == ("engine.step", None)
    for name in STEP_SPANS[1:-1]:
        assert {p for n, p, _ in got if n == name} == {"engine.step"}
    assert ({p for n, p, _ in got if n == "engine.sample"}
            == {"engine.prefill", "engine.decode"})
    # both prompts pad to one bucket: one prefill group with both uids
    (attrs,) = [a for n, _, a in got if n == "engine.prefill"]
    assert attrs == {"uids": f"{uids[0]} {uids[1]}", "padded": 16,
                     "tokens": 8}
    assert [a for n, _, a in got if n == "engine.decode"] == [{"lanes": 2}]
    for name, (count, secs, self_s, _, _) in eng.spans.items():
        assert count >= 1 and 0 <= self_s <= secs, name
    step = eng.spans["engine.step"]
    children = sum(eng.spans[n][1] for n in STEP_SPANS[1:-1])
    assert children <= step[1]
    assert step[2] == pytest.approx(step[1] - children, abs=1e-9)


def test_decode_seconds_is_the_decode_span_total(tiny):
    eng = make_engine(tiny)
    for p in ([5, 9, 2, 4, 11], [1, 2, 3], [7, 7, 7, 7]):
        eng.submit(p, 5)
    eng.run()
    count, secs = eng.spans["engine.decode"][:2]
    assert count == eng.decode_iterations > 0
    assert eng.decode_seconds == secs


def test_queue_admissions_count_a_preempted_request_once(tiny):
    prefix = [5, 9, 2, 4, 11, 3, 8, 1]
    prompts = [prefix + [7, 6], prefix + [10, 12], prefix + [7, 6],
               [1, 2, 3], prefix + [13, 14, 15], prefix + [7, 6]]
    eng = make_engine(tiny, kv_pool_blocks=7)
    for p in prompts:
        eng.submit(p, 8)
    eng.run()
    assert eng.block_mgr.stats()["preemptions"] > 0
    # resumes re-prefill, yet each request is admitted from the queue once
    assert eng.prefill_tokens > sum(map(len, prompts))
    assert eng.queue_admissions == len(prompts)
    assert eng.queue_wait_seconds > 0


def test_submit_times_arrival_and_ttft_from_one_reading(tiny):
    eng = make_engine(tiny)
    uid = eng.submit([1, 2, 3], 2)
    assert eng.sched.waiting[0].arrived_at == eng._t0[uid]


@pytest.fixture
def fresh_jit_state(monkeypatch):
    monkeypatch.setattr(trace, "_covered", [])
    monkeypatch.setattr(trace, "OUTSIDE", [0.0, 0])


def test_jit_events_go_to_the_innermost_span_counted_once(
        fresh_jit_state, monkeypatch):
    now = [0.0]
    monkeypatch.setattr(trace, "clock", lambda: now[0])
    table = trace.SpanTable()
    trace_ev, compile_ev = trace.JIT_EVENTS[0], trace.JIT_EVENTS[2]
    with table.span("engine.step"):
        with table.span("engine.prefill"):
            now[0] = 2.0     # an inner jit traced over [1, 2] ...
            jax.monitoring.record_event_duration_secs(trace_ev, 1.0)
            now[0] = 5.0     # ... inside its caller's trace over [0, 5]
            jax.monitoring.record_event_duration_secs(trace_ev, 5.0)
            jax.monitoring.record_event_duration_secs("/other/event", 9.0)
        now[0] = 7.0
        jax.monitoring.record_event_duration_secs(compile_ev, 1.5)
    now[0] = 9.0
    jax.monitoring.record_event_duration_secs(compile_ev, 0.5)
    assert table["engine.prefill"][3:] == [5.0, 2]
    assert table["engine.step"][3:] == [1.5, 1]
    assert trace.OUTSIDE == [0.5, 1]


def test_eager_scan_under_prefill_is_charged_to_it(fresh_jit_state):
    table = trace.SpanTable()

    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2.0

    @jax.jit
    def outer(x):
        return inner(x) + 1.0

    with table.span("engine.prefill") as sp:
        # an eager scan traces its body anew on every call
        for _ in range(2):
            jax.lax.scan(lambda c, x: (c + x, c), 0.0,
                         jnp.arange(4.0))[0].block_until_ready()
        outer(jnp.ones(3)).block_until_ready()
    count, secs, self_s, jit_s, jit_n = table["engine.prefill"]
    assert jit_n >= 4 and 0 < jit_s <= sp.seconds
    assert "engine.step" not in table


def _op_count(hlo: str) -> int:
    return len(re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = ", hlo, re.M))


def test_named_scopes_leave_the_compiled_decode_step_unchanged(
        tiny, monkeypatch):
    """The ``sail_mm`` and ``attn`` scopes rename ops, nothing else: the
    optimized decode step has as many ops with them as without."""
    eng = make_engine(tiny)
    args = (eng.params, jnp.zeros((4, 1), jnp.int32), eng.cache, eng.cfg)
    kw = dict(quant_kv=True, active_mask=jnp.ones((4,), bool),
              block_tables=jnp.asarray(eng._tables_np))

    def compiled():
        # a persistent-cache key leaves op names out: compile afresh
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            jax.clear_caches()
            return lm.decode_step.lower(*args, **kw).compile().as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", was)

    scoped = compiled()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled()
    assert "sail_mm" in scoped and "/attn/" in scoped
    assert "sail_mm" not in plain and "/attn/" not in plain
    assert _op_count(scoped) == _op_count(plain) > 100


def _live_blocks_by_hand(requests, windows, block):
    """Blocks a decode step attends, summed over steps, lanes and layers:
    a request of prompt ``n`` and ``new`` tokens decodes at positions
    ``n .. n + new - 2`` (prefill gives its first token), and at
    position ``p`` a layer of window ``w`` reads blocks ``max(0, p - w +
    1) // block`` through ``p // block``."""
    return sum(p // block - max(0, p - w + 1) // block + 1
               for n, new in requests for p in range(n, n + new - 1)
               for w in windows)


@pytest.mark.parametrize("arch,windows", [
    ("tinymistral_248m", None),
    ("mellum2_12b_a2_5b", (8, 8, 8, 1 << 30)),
], ids=["no_window", "sliding_and_full"])
def test_decode_attn_counts_live_blocks(arch, windows):
    """``stats()["decode_attn"]`` on the CPU: no step runs the kernel,
    ``blocks_read`` is the hand count of the lanes' live blocks (a model
    without a window reads every block up to its position; Mellum2's
    sliding layers only the last 8 positions'), and ``blocks_in_tables``
    is every lane's whole table in every layer of every step."""
    import dataclasses
    cfg = C.get_smoke(arch)
    if windows is None:
        cfg = dataclasses.replace(cfg, window=None)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    block, batch = 4, 2
    eng = Engine(params, cfg, EngineConfig(
        batch_size=batch, cache_len=48, kv_block_size=block, quantize=True,
        group_size=32, plan="uniform:4a8", eos_token=-1))
    requests = ((13, 9), (21, 14), (6, 5))
    for n, new in requests:
        eng.submit([(7 * i + n) % cfg.vocab for i in range(n)], new)
    eng.run()
    got = eng.stats()["decode_attn"]
    windows = windows or (48,) * cfg.n_layers
    assert got["kernel_steps"] == 0
    assert got["blocks_read"] == _live_blocks_by_hand(requests, windows,
                                                      block) > 0
    assert got["blocks_in_tables"] == (eng.decode_iterations * batch
                                       * (48 // block) * cfg.n_layers)
    assert got["blocks_read"] < got["blocks_in_tables"]


@pytest.mark.parametrize("arch", ["tinymistral_248m", "mellum2_12b_a2_5b"])
def test_second_admission_at_a_shape_compiles_nothing(arch):
    """Prefill is one program per (batch, padded length): after one
    admission at a shape, a second at the same shape, with another
    prompt, traces and compiles nothing (no backend compile, no JIT
    event in ``engine.prefill``) and is not a new shape in
    ``stats()["prefill"]``."""
    cfg = C.get_smoke(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, EngineConfig(
        batch_size=4, cache_len=64, quantize=True, group_size=32,
        plan="uniform:4a8", kv_block_size=8, eos_token=-1))
    assert eng.paged and eng._quant_kv
    eng.submit([(5 * i + 3) % cfg.vocab for i in range(12)], 4)
    eng.step()
    first = eng.stats().get("prefill")
    jit_events = eng.spans["engine.prefill"][4]
    compiles = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        eng.submit([(7 * i + 1) % cfg.vocab for i in range(11)], 4)
        eng.step()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
    assert eng.spans["engine.prefill"][0] == 2
    assert eng.spans["engine.prefill"][4] == jit_events
    assert first == {"calls": 1, "new_shapes": 1}
    assert eng.stats()["prefill"] == {"calls": 2, "new_shapes": 1}
