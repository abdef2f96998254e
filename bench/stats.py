"""Window arithmetic: rates, percentiles and the end-to-end metrics.

Every request is timed from the moment it was due: for an open loop its
scheduled arrival, for a closed loop the moment its client received the
previous request's last token.  The loop submits a due request before
its next engine step, so a step that stalls the loop shows up as
waiting in the time to first token of every request due meanwhile.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence


@dataclass
class Timed:
    """One request as the client sees it."""
    due: float
    prompt_len: int
    max_new: int
    prompt: List[int] = field(default_factory=list)
    times: List[float] = field(default_factory=list)   # token arrivals
    tokens: List[int] = field(default_factory=list)
    failed: bool = False

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.max_new

    @property
    def finished_at(self) -> Optional[float]:
        return self.times[-1] if self.done else None


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile (numpy's default method)."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttfts(reqs: Sequence[Timed], t_open: float, t_close: float) -> List[float]:
    """First token minus due time, over requests due in the window.  A
    request with no first token by the close counts at close minus due
    (censored: the true value is at least that)."""
    out = []
    for r in reqs:
        if t_open <= r.due < t_close and not r.failed:
            first = r.times[0] if r.times and r.times[0] <= t_close else None
            out.append((first if first is not None else t_close) - r.due)
    return out


def itls(reqs: Sequence[Timed], t_open: float, t_close: float) -> List[float]:
    """Gaps between consecutive tokens of a request, both inside the
    window."""
    out = []
    for r in reqs:
        ts = [t for t in r.times if t_open <= t <= t_close]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def tokens_in(reqs: Sequence[Timed], t_open: float, t_close: float) -> int:
    return sum(1 for r in reqs for t in r.times if t_open <= t <= t_close)


def end_to_end(reqs: Sequence[Timed], t_open: float,
               t_close: float) -> Dict[str, float]:
    """The served-request metrics of one window."""
    span = t_close - t_open
    done = [r for r in reqs
            if r.done and t_open <= r.finished_at <= t_close]
    ft, gaps = ttfts(reqs, t_open, t_close), itls(reqs, t_open, t_close)
    return {
        "output_tok_s": tokens_in(reqs, t_open, t_close) / span,
        "ttft_p95_ms": percentile(ft, 95) * 1e3 if ft else None,
        "itl_p95_ms": percentile(gaps, 95) * 1e3 if gaps else None,
        "completed": len(done),
        "ttft_samples": len(ft),
        "itl_samples": len(gaps),
    }
