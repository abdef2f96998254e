"""The client loop that drives the serving entry.

It talks to the program only through ``Engine.submit`` (with an
``on_token`` callback) and ``Engine.step``.  Each pass submits every
request whose due time has passed, then runs one engine step; with
nothing in flight it sleeps until the next request is due.  Token
arrival times are taken in the callback.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, List, Optional

import numpy as np

from bench.stats import Timed
from bench.traffic import Request

clock = time.perf_counter

# JAX monitoring event recorded when XLA compiles a program: a program
# neither this process nor the persistent cache has.  (A program taken
# from the persistent cache records none.)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Times at which XLA compiled a program."""

    def __init__(self):
        self.compiled: List[float] = []

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if event == BACKEND_COMPILE:
            self.compiled.append(clock())

    def count(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.compiled)


_LOG: Optional[CompileLog] = None


def compile_log() -> CompileLog:
    """The process's compile log (JAX's listeners cannot be removed, so
    one is registered per process)."""
    global _LOG
    if _LOG is None:
        import jax
        _LOG = CompileLog()
        jax.monitoring.register_event_duration_secs_listener(_LOG)
    return _LOG


class Loop:
    """Open or closed loop over one engine."""

    def __init__(self, eng, traffic: Dict, requests: Iterator[Request],
                 traced: bool = False):
        self.eng = eng
        self.traffic = traffic
        self.requests = requests
        self.traced = traced
        self.reqs: List[Timed] = []
        self.inflight: Dict[int, Timed] = {}
        self.steps: List[Dict] = []
        self.due: List[float] = []       # sorted due times of next requests
        self.t_start: Optional[float] = None
        self.waiting_clients = 0         # closed loop: clients not yet started

    def span(self, name: str):
        if not self.traced:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    # --- arrivals ----------------------------------------------------------
    def start(self) -> None:
        """Traffic begins now.  An open loop schedules its first arrival;
        a closed loop starts its first client, and each further client
        once the one before it has its first token, so that clients
        join one prefill at a time rather than in one burst."""
        self.t_start = clock()
        if self.traffic["loop"] == "open":
            self._next = next(self.requests)
            self.due = [self.t_start + self._next.gap_s]
        else:
            self.waiting_clients = int(self.traffic["clients"]) - 1
            self.due = [self.t_start]

    @property
    def ramped(self) -> bool:
        return self.traffic["loop"] == "open" or not self.waiting_clients

    def _submit_due(self, now: float) -> None:
        while self.due and self.due[0] <= now:
            due = self.due.pop(0)
            if self.traffic["loop"] == "open":
                req, self._next = self._next, next(self.requests)
                self.due.append(due + self._next.gap_s)
            else:
                req = next(self.requests)
            self._submit(req, due)

    def _submit(self, req: Request, due: float) -> None:
        r = Timed(due=due, prompt_len=len(req.prompt), max_new=req.max_new,
                  prompt=req.prompt)
        self.reqs.append(r)
        with self.span("bench.submit"):
            try:
                uid = self.eng.submit(req.prompt, req.max_new,
                                      on_token=self._on_token)
            except ValueError:
                r.failed = True
                return
        self.inflight[uid] = r

    def _on_token(self, uid: int, tok: int) -> None:
        now = clock()
        r = self.inflight[uid]
        r.times.append(now)
        r.tokens.append(int(tok))
        if len(r.times) == 1 and not self.ramped:
            self.waiting_clients -= 1
            self.due.append(now)
            self.due.sort()
        if r.done:
            del self.inflight[uid]
            if self.traffic["loop"] == "closed":
                self.due.append(now)
                self.due.sort()

    # --- the loop ------------------------------------------------------------
    def step(self) -> None:
        e = self.eng
        before = (e.prefill_tokens, e.decode_seconds, e.decode_iterations)
        t0 = clock()
        with self.span("bench.step"):
            e.step()
        t1 = clock()
        decoded = e.decode_iterations > before[2]
        self.steps.append({
            "t0": t0, "t1": t1,
            "prefill_tokens": e.prefill_tokens - before[0],
            "decode_s": e.decode_seconds - before[1],
            # positions each decoding lane attended to in this step
            "contexts": ([r.prompt_len + len(r.tokens)
                          for r in self.inflight.values() if r.tokens]
                         if decoded else []),
        })

    def run(self, t_end: float) -> None:
        while True:
            now = clock()
            if now >= t_end:
                return
            self._submit_due(now)
            if self.inflight:
                self.step()
            else:
                wait = min(self.due[0] if self.due else t_end, t_end) - now
                with self.span("bench.wait_arrival"):
                    time.sleep(max(wait, 0.0))


def warm_shapes(eng, traffic: Dict, vocab: int) -> None:
    """Run one prefill at every padded prompt length the mix can produce,
    at each batch size in ``warmup.prefill_batches``, then decode once.
    Compiles (or loads from the persistent cache) what the window uses."""
    bucket = traffic["engine"]["prompt_bucket"]
    lo, hi = traffic["prompt"]["low"], traffic["prompt"]["high"]
    lengths = range(-(-lo // bucket) * bucket, -(-hi // bucket) * bucket + 1,
                    bucket)
    rng = np.random.default_rng(0)
    for b in traffic["warmup"]["prefill_batches"]:
        for t in lengths:
            for _ in range(b):
                eng.submit(rng.integers(0, vocab, size=t).tolist(), 2)
            while eng.step():
                pass


def warm_traffic(loop: Loop) -> None:
    """Run the cell's own traffic until every client has started, then
    ``warmup.seconds`` more, so that the window opens on a loop in its
    steady state."""
    loop.start()
    while not loop.ramped:
        loop.run(clock() + 0.5)
    loop.run(clock() + loop.traffic["warmup"]["seconds"])
