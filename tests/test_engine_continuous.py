"""Continuous-batching engine: equivalence with the plain run-to-completion
reference (``lm.greedy_generate``), iteration-granular backfill on staggered arrivals, slot reuse, streaming,
and the decode-phase stats the benchmarks report."""
import jax
import numpy as np
import pytest

import repro.configs as C
from repro.models import lm
from repro.serving.engine import Engine, EngineConfig


@pytest.fixture(scope="module")
def tiny():
    cfg = C.get_smoke("tinymistral_248m")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_engine(tiny, batch=4, **kw):
    cfg, params = tiny
    return Engine(params, cfg, EngineConfig(
        batch_size=batch, cache_len=64, quantize=True, ql=4,
        group_size=32, quant_kv=True, **kw))


PROMPTS = [[1, 2, 3], [4, 5, 6, 7], [8, 9], [3, 1, 4, 1, 5], [2, 7, 1]]


def test_continuous_matches_run_to_completion(tiny):
    """Greedy outputs must be token-identical to the plain one-prompt
    reference loop over the engine's own quantized weights: the slot
    pool + masked decode change WHEN work runs, never WHAT is computed."""
    cfg, _ = tiny
    eng = make_engine(tiny)
    uids = [eng.submit(p, max_new_tokens=6) for p in PROMPTS]
    done = {c.uid: c.tokens for c in eng.run()}
    for uid, p in zip(uids, PROMPTS):
        ref = lm.greedy_generate(eng.params, np.asarray([p], np.int32), cfg,
                                 6, cache_len=64, quant_kv=True)
        assert done[uid] == np.asarray(ref[0]).tolist(), p


def test_staggered_arrival_backfills_mid_decode(tiny):
    """A request arriving mid-decode must join the running batch at the
    next iteration, not wait for the cohort to drain."""
    max_new = 24
    cohort = [[1, 2, 3], [4, 5, 6, 7], [8, 9]]
    late = [7, 7, 7]

    eng = make_engine(tiny)
    uids = [eng.submit(p, max_new) for p in cohort]
    for _ in range(4):
        assert eng.step()
    late_uid = eng.submit(late, max_new)
    eng.run()
    ev = eng.events
    cohort_finish = max(ev[u]["finished_iteration"] for u in uids)
    assert ev[late_uid]["first_decode_iteration"] < cohort_finish, \
        "late request must start decoding before the first cohort finishes"
    assert eng.stats()["generated_tokens"] == 4 * max_new


def test_more_requests_than_slots_reuses_slots(tiny):
    """7 requests through a 2-slot pool: every slot is recycled and every
    request completes with the full token budget."""
    eng = make_engine(tiny, batch=2)
    for i in range(7):
        eng.submit([i + 1, 2, 3], max_new_tokens=3)
    done = eng.run()
    assert len(done) == 7
    assert all(len(c.tokens) == 3 for c in done)
    assert eng.sched.free_slots == [0, 1]          # pool fully drained


def test_streaming_callback_order(tiny):
    """on_token streams each request's tokens in generation order."""
    eng = make_engine(tiny)
    streamed = {}
    cb = lambda uid, tok: streamed.setdefault(uid, []).append(tok)
    uids = [eng.submit(p, 5, on_token=cb) for p in PROMPTS[:3]]
    done = {c.uid: c.tokens for c in eng.run()}
    assert set(streamed) == set(uids)
    for uid in uids:
        assert streamed[uid] == done[uid]


def test_eos_retires_slot_early(tiny):
    """A request hitting EOS frees its slot before max_new_tokens."""
    cfg, params = tiny
    eng = make_engine(tiny, batch=2)
    # first learn what the model emits, then use that token as EOS
    probe = make_engine(tiny, batch=2)
    probe.submit([1, 2, 3], 4)
    first = probe.run()[0].tokens[0]
    eng.ecfg.eos_token = first
    uid = eng.submit([1, 2, 3], max_new_tokens=64)
    done = eng.run()
    assert len(done) == 1
    assert done[0].tokens[-1] == first
    assert len(done[0].tokens) < 64


def test_stats_decode_phase_breakdown(tiny):
    """stats() must separate prefill from decode so benchmarks can report
    paper-relevant decode-phase throughput, plus per-request TTFT."""
    eng = make_engine(tiny)
    for p in PROMPTS[:4]:
        eng.submit(p, max_new_tokens=5)
    done = eng.run()
    st = eng.stats()
    assert st["prefill_tokens"] == sum(len(p) for p in PROMPTS[:4])
    # the simultaneous burst pads to one bucket -> ONE batched prefill
    # pass (weights streamed once for all four admissions)
    assert st["prefill_iterations"] == 1
    assert st["decode_iterations"] > 0
    assert st["iterations"] == (st["prefill_iterations"]
                                + st["decode_iterations"])
    assert st["generated_tokens"] == 4 * 5
    assert st["mean_ttft_s"] > 0.0
    assert all(0.0 < c.ttft_s <= c.latency_s for c in done)


def test_prefill_budget_staggers_admission(tiny):
    """With a tight prefill budget, a burst of prompts is admitted across
    several iterations instead of all at once."""
    eng = make_engine(tiny, prefill_budget=4)
    for p in PROMPTS[:4]:                      # prompt lens 3, 4, 2, 5
        eng.submit(p, max_new_tokens=3)
    eng.step()
    first_admitted = eng.prefill_iterations
    assert first_admitted < 4                  # budget split the burst
    done = eng.run()
    assert len(done) == 4                      # but everyone finishes


def test_recurrent_family_slot_serving():
    """ssm-family prefill is exact-length (bucket padding would fold pad
    tokens into the recurrent state): equal-length prompts must match the
    plain reference loop exactly, ragged prompts must still complete."""
    cfg = C.get_smoke("xlstm_350m")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    mk = lambda: Engine(params, cfg, EngineConfig(
        batch_size=2, cache_len=32, quantize=False, quant_kv=False))
    eng = mk()
    prompts = ([1, 2, 3], [4, 5, 6], [7, 8, 9])
    uids = [eng.submit(p, max_new_tokens=3) for p in prompts]
    done = {c.uid: c.tokens for c in eng.run()}
    for uid, p in zip(uids, prompts):
        ref = lm.greedy_generate(params, np.asarray([p], np.int32), cfg, 3,
                                 cache_len=32)
        assert done[uid] == np.asarray(ref[0]).tolist(), p
    eng = mk()
    for p in ([1, 2], [3, 4, 5, 6], [7]):
        eng.submit(p, max_new_tokens=3)
    done = eng.run()
    assert len(done) == 3 and all(len(c.tokens) == 3 for c in done)


def test_zero_max_new_tokens_yields_empty_completion(tiny):
    """max_new_tokens=0 must yield an empty completion without holding
    back the request beside it."""
    eng = make_engine(tiny, batch=2)
    uid = eng.submit([1, 2, 3], max_new_tokens=0)
    uid2 = eng.submit([4, 5], max_new_tokens=3)
    done = {c.uid: c.tokens for c in eng.run()}
    assert done[uid] == []
    assert len(done[uid2]) == 3
