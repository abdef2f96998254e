"""Tensor-level scheduling / ping-pong pipeline planner + PRT sim."""
import numpy as np
import pytest
from _hyp import given, settings, strategies as st

from repro.core.scheduler import (IterationScheduler, PipelineModel,
                                  Request, plan_tensor_schedule)
from repro.core import pattern


def test_tensor_schedule_alternates_buffers():
    layers = [[("w1", 100), ("w2", 50)], [("w3", 120)], [("w4", 60)]]
    sched = plan_tensor_schedule(layers, buffer_bytes=400)
    assert sched.n_phases == 3
    buffers = [sched.residency(i)[0].buffer for i in range(3)]
    assert buffers == [0, 1, 0]


def test_tensor_schedule_splits_oversized_layer():
    layers = [[("big1", 150), ("big2", 150)]]   # 300 > half (200/2=... )
    sched = plan_tensor_schedule(layers, buffer_bytes=400)
    assert sched.n_phases == 2                   # split into two tiles


def test_pipeline_bubble_free_batch():
    pm = PipelineModel(stream_bw=100.0, compute_rate=1000.0)
    # write time = b/100; compute at B: B*b/1000 -> balanced at B=10
    assert pm.bubble_free_batch(1000) == 10


def test_pipeline_optimal_batch_knee():
    # paper: throughput plateaus around batch ~8 for its machine balance
    pm = PipelineModel(stream_bw=204.8e9, compute_rate=204.8e9 * 8)
    b = pm.optimal_batch(32 << 20)
    assert 6 <= b <= 10


def test_iteration_scheduler_backfill():
    s = IterationScheduler(max_batch=2)
    for i in range(4):
        s.submit(Request(uid=i, prompt_len=4, max_new_tokens=2))
    batch = s.schedule()
    assert [r.uid for r in batch] == [0, 1]
    assert s.schedule() == []    # no free slot until a release
    for uid in (0, 1):
        s.release(uid)
    assert {r.uid for r in s.finished} == {0, 1}
    batch = s.schedule()
    assert [r.uid for r in batch] == [2, 3]
    assert sorted(r.slot for r in batch) == [0, 1]


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 12), tgt=st.integers(1, 5))
def test_property_scheduler_conserves_requests(n, tgt):
    s = IterationScheduler(max_batch=tgt)
    for i in range(n):
        s.submit(Request(uid=i, prompt_len=1, max_new_tokens=1))
    guard = 0
    while not s.idle():
        s.schedule()
        for r in list(s.running):
            s.release(r.uid)
        guard += 1
        assert guard < 100
    assert len(s.finished) == n


def test_prt_capacity_eviction():
    # more unique (group, pattern) keys than entries forces misses
    pats = np.arange(64).reshape(1, 1, 64) % 16   # 64 groups, 1 batch
    st_ = pattern.prt_simulate(np.tile(pats, (1, 1, 1)), entries=8)
    assert st_.hit_rate == 0.0
    # batch 4 with identical rows: 3 of 4 accesses hit per (group, plane)
    pats4 = np.tile(pats, (4, 1, 1))
    st4 = pattern.prt_simulate(pats4, entries=1024)
    assert st4.hit_rate == pytest.approx(0.75)


# ---------------------------------------------------------------------------
# slot-based continuous scheduling (property-style, via the _hyp shim)
# ---------------------------------------------------------------------------

def _drain_continuous(s, decode_steps_fn):
    """Drive schedule()/release() to completion; returns iteration trace."""
    trace = []
    guard = 0
    while not s.idle():
        admitted = s.schedule()
        trace.append({"admitted": [r.uid for r in admitted],
                      "running": len(s.running),
                      "admitted_tokens": sum(r.prompt_len
                                             for r in admitted)})
        for r in list(s.running):
            r.generated += 1
            if r.generated >= r.max_new_tokens:
                s.release(r.uid)
        guard += 1
        assert guard < 10_000
    return trace


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 20), slots=st.integers(1, 6),
       seed=st.integers(0, 99))
def test_property_slots_never_exceed_max_batch(n, slots, seed):
    rng = np.random.default_rng(seed)
    s = IterationScheduler(max_batch=slots)
    for i in range(n):
        s.submit(Request(uid=i, prompt_len=int(rng.integers(1, 9)),
                         max_new_tokens=int(rng.integers(1, 5))))
    for step in _drain_continuous(s, None):
        assert step["running"] <= slots
    used = [r.slot for r in s.running]
    assert len(s.free_slots) == slots and sorted(s.free_slots) == \
        list(range(slots)) and not used


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 25), slots=st.integers(1, 5),
       seed=st.integers(0, 99))
def test_property_every_uid_finishes_exactly_once(n, slots, seed):
    rng = np.random.default_rng(seed)
    s = IterationScheduler(max_batch=slots)
    for i in range(n):
        s.submit(Request(uid=i, prompt_len=1,
                         max_new_tokens=int(rng.integers(1, 6))))
    _drain_continuous(s, None)
    finished = [r.uid for r in s.finished]
    assert sorted(finished) == list(range(n))          # all, exactly once
    assert all(r.done and r.state == "done" for r in s.finished)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 16), budget=st.integers(1, 12),
       seed=st.integers(0, 99))
def test_property_prefill_budget_cap(n, budget, seed):
    rng = np.random.default_rng(seed)
    s = IterationScheduler(max_batch=8, prefill_budget=budget)
    for i in range(n):
        s.submit(Request(uid=i, prompt_len=int(rng.integers(1, 10)),
                         max_new_tokens=2))
    for step in _drain_continuous(s, None):
        if len(step["admitted"]) > 1:
            # beyond the exempt first request, the cap holds
            assert step["admitted_tokens"] <= budget


@settings(max_examples=15, deadline=None)
@given(slots=st.integers(1, 4), waves=st.integers(2, 4))
def test_property_freed_slots_are_reused(slots, waves):
    s = IterationScheduler(max_batch=slots)
    for i in range(slots * waves):
        s.submit(Request(uid=i, prompt_len=1, max_new_tokens=1))
    seen_slots = []
    while not s.idle():
        for r in s.schedule():
            seen_slots.append(r.slot)
        for r in list(s.running):
            r.generated += 1
            if r.generated >= r.max_new_tokens:
                s.release(r.uid)
    # every wave reuses the same physical slots
    assert sorted(set(seen_slots)) == list(range(slots))
    assert len(seen_slots) == slots * waves


def test_release_unknown_uid_raises():
    s = IterationScheduler(max_batch=2)
    s.submit(Request(uid=1, prompt_len=1, max_new_tokens=1))
    s.schedule()
    with pytest.raises(KeyError):
        s.release(99)
