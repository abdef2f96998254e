"""Window arithmetic of the serving benchmark: rates, percentiles and the
censored time to first token."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import stats, traffic  # noqa: E402
from bench.stats import Timed  # noqa: E402


@pytest.mark.parametrize("values,q", [([3.0], 95), ([1, 2, 3, 4], 50),
                                      (list(range(100)), 95),
                                      ([5, 1, 9, 2, 7], 95)])
def test_percentile_matches_numpy(values, q):
    assert stats.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


def test_ttft_counts_from_due_time_and_censors_at_close():
    reqs = [Timed(due=1.0, prompt_len=4, max_new=2, times=[1.5, 1.6],
                  tokens=[1, 2]),
            Timed(due=2.0, prompt_len=4, max_new=2),        # no token yet
            Timed(due=9.0, prompt_len=4, max_new=2, times=[9.2]),
            Timed(due=0.5, prompt_len=4, max_new=2, times=[1.2])]  # before
    got = stats.ttfts(reqs, t_open=1.0, t_close=5.0)
    assert got == pytest.approx([0.5, 3.0])


def test_itl_keeps_gaps_with_both_ends_inside_the_window():
    r = Timed(due=0.0, prompt_len=1, max_new=5,
              times=[0.5, 1.5, 2.0, 4.0, 6.0], tokens=[1] * 5)
    assert stats.itls([r], 1.0, 5.0) == pytest.approx([0.5, 2.0])
    assert stats.tokens_in([r], 1.0, 5.0) == 3


def test_end_to_end_rates_and_counts():
    done = Timed(due=1.0, prompt_len=2, max_new=3, times=[1.2, 1.25, 1.3],
                 tokens=[1, 2, 3])
    early = Timed(due=0.0, prompt_len=2, max_new=2, times=[0.5, 2.0],
                  tokens=[1, 2])
    unfinished = Timed(due=3.0, prompt_len=2, max_new=4, times=[3.1],
                       tokens=[1])
    e = stats.end_to_end([done, early, unfinished], 1.0, 5.0)
    assert e["output_tok_s"] == pytest.approx(5 / 4.0)
    assert e["completed"] == 2
    assert e["ttft_samples"] == 2
    assert e["ttft_p95_ms"] == pytest.approx(
        np.percentile([200.0, 100.0], 95))
    assert e["itl_samples"] == 2


def test_failed_request_is_attempted_but_has_no_ttft():
    r = Timed(due=1.0, prompt_len=2, max_new=1, failed=True)
    assert stats.ttfts([r], 0.0, 5.0) == []


def _mix(loop="closed"):
    return {"loop": loop, "clients": 2, "rate_req_s": 4.0, "strata": 8,
            "prompt": {"median": 20, "sigma": 0.8, "low": 4, "high": 64},
            "output": {"median": 10, "sigma": 0.6, "low": 2, "high": 30},
            "engine": {"cache_len": 94}}


def test_every_seed_draws_the_same_lengths_in_another_order():
    def block(seed):
        s = traffic.stream(_mix("open"), 100, seed)
        return [next(s) for _ in range(8)]
    a, b = block(1), block(2**33 + 1)
    key = lambda rs: (sorted(len(r.prompt) for r in rs),
                      sorted(r.max_new for r in rs),
                      sorted(round(r.gap_s, 12) for r in rs))
    assert key(a) == key(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert sum(r.gap_s for r in a) == pytest.approx(8 / 4.0)
    assert all(0 <= t < 100 for r in a for t in r.prompt)


def test_same_seed_same_stream():
    s1, s2 = (traffic.stream(_mix(), 50, 7) for _ in range(2))
    for _ in range(20):
        x, y = next(s1), next(s2)
        assert (x.prompt, x.max_new) == (y.prompt, y.max_new)


def test_traffic_validation_refuses_requests_longer_than_the_cache():
    mix = _mix()
    mix["engine"]["cache_len"] = 50
    with pytest.raises(ValueError, match="KV positions"):
        traffic.validate(mix)
