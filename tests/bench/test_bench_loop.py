"""The client loop against a scripted engine: when requests are due, how
closed-loop clients join, and when tokens are timed."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import serve, traffic  # noqa: E402


class ScriptedEngine:
    """Admits everything submitted at its next step and gives each
    request one token per step; a step takes ``step_s`` of the loop's
    clock."""

    def __init__(self, clock, step_s=0.1):
        self.clock, self.step_s = clock, step_s
        self.reqs, self.uid = {}, 0
        self.prefill_tokens = self.decode_iterations = 0
        self.decode_seconds = 0.0

    def submit(self, prompt, max_new, on_token):
        self.uid += 1
        self.reqs[self.uid] = [max_new, on_token]
        return self.uid

    def step(self):
        self.clock.t += self.step_s
        self.decode_iterations += 1
        for uid, r in list(self.reqs.items()):
            r[0] -= 1
            r[1](uid, 7)
            if r[0] == 0:
                del self.reqs[uid]
        return bool(self.reqs)


class Clock:
    t = 100.0

    def __call__(self):
        return self.t


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(serve, "clock", c)
    monkeypatch.setattr(serve.time, "sleep", lambda s: setattr(c, "t", c.t + s))
    return c


def _mix(loop, **kw):
    m = {"loop": loop, "clients": 3, "rate_req_s": 2.0, "strata": 4,
         "prompt": {"median": 8, "sigma": 0.5, "low": 4, "high": 16},
         "output": {"median": 3, "sigma": 0.0, "low": 3, "high": 3},
         "engine": {"cache_len": 64}, "warmup": {"seconds": 1.0}}
    m.update(kw)
    return m


def test_closed_loop_clients_join_one_at_a_time_then_follow_completions(clock):
    mix = _mix("closed")
    eng = ScriptedEngine(clock)
    loop = serve.Loop(eng, mix, traffic.stream(mix, 50, 1))
    serve.warm_traffic(loop)
    assert loop.ramped
    dues = [r.due for r in loop.reqs[:3]]
    # each client is due when the one before it got its first token
    assert dues == pytest.approx([100.0, 100.1, 100.2])
    for r in loop.reqs:
        if r.done and r.finished_at < clock.t:
            nxt = [q for q in loop.reqs if q.due == r.finished_at]
            assert nxt, "a finished client sends its next request"
    # every request is timed from its due time, one token per step
    r = loop.reqs[0]
    assert r.times == pytest.approx([100.1, 100.2, 100.3])


def test_open_loop_submits_on_its_schedule_and_sleeps_when_idle(clock):
    mix = _mix("open", rate_req_s=0.5)
    eng = ScriptedEngine(clock)
    loop = serve.Loop(eng, mix, traffic.stream(mix, 50, 2))
    loop.start()
    loop.run(clock.t + 40.0)
    # a block of ``strata`` arrivals spans strata / rate seconds
    assert loop.reqs[3].due - 100.0 == pytest.approx(4 / 0.5)
    # a due request waits at most for the step in flight, then its own
    assert all(r.times[0] - r.due <= 0.2 + 1e-9 for r in loop.reqs
               if r.times)
    assert all(r.times for r in loop.reqs[:-1])
    # contexts: what each decoding lane attended to, step by step
    assert all(c >= 4 for s in loop.steps for c in s["contexts"])
