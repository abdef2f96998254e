#!/usr/bin/env python3
"""Serving benchmark: one cell of ``BENCHMARK.json`` on the attached chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``: the model
and the plain reference beside it) and a traffic mix
(``bench/traffic/<traffic>.json``: loop, lengths, engine sizes, precision
plan, warm-up and the correctness sample).  The run

1. fails, printing no result, unless JAX sees an accelerator with at
   least the chips the cell asks for and ``bench/peaks.json`` knows it;
2. makes the weights on the device from the seed, builds
   ``repro.serving.engine.Engine`` with the cell's plan, and warms up:
   every padded prompt length the mix can produce, then the mix itself
   until every client has started and a few seconds more (this is
   ``setup_s``);
3. drives the engine for ``--seconds`` through ``submit``/``step`` only;
4. frees the engine and compares a sample of the finished requests,
   drawn from the seed with the longest among them, with the plain
   reference: the widest gap by which a served token's reference logit
   lies below the reference's best must stay under the cell's limit.

The last line of standard output is one JSON object.  With ``--trace 0``
its metrics are the cell's end-to-end metrics; with ``--trace 1`` a
profiler trace of the window feeds the per-layer metrics, one reader per
metric in ``bench/metrics/<name>.py``.

A configuration file holds the model (``model``: every ``ModelConfig``
field) and names what the harness finds beside it, so that a new model
brings new files and no edit here:

- ``reference``: its plain reference, ``bench/reference/<name>.py``
  (``make_params(m, seed)``, ``logits(params, seq, prompt_len, m,
  prec)``, ``gaps(logits, tokens)``);
- ``counts`` (optional): its work counts, ``bench/work/<name>.py``, with
  ``decode_step(m, prec, contexts) -> (flops, bytes)`` of one step,
  ``prefill_flops(m, prompt_len)`` and, optionally,
  ``decode_scopes(m, prec, steps, stats) -> {scope: (flops, bytes)}``
  over the window's decode steps; without the key, ``bench/counts.py``;
- ``scopes`` (optional): the ``named_scope``s its program opens inside
  the decode step; a traced run splits that step's op time over them
  and ``other``.

A reader's ``read(ctx)`` returns a number, or None where it finds
nothing to read.  ``ctx`` is built for traced runs only, around the
window, so an untraced run times what it always did:

- ``window_s``: the window's host seconds; ``compiles``: XLA compiles
  in it; ``memory_peak_bytes``; ``peaks``: the device's row of
  ``bench/peaks.json``;
- ``counters``: the window's change in the engine's
  ``decode_iterations``, ``decode_seconds``, ``decode_tokens``,
  ``prefill_tokens`` and ``prefill_iterations``; ``prefill_host_s``:
  host seconds of the steps that prefilled, less their decode seconds;
- ``trace``: ``trace_reduce.reduce`` (``busy_s``, ``window_s``,
  ``modules``, ``device_ops``, ``idle_gaps``) and ``trace_spans.reduce``
  (``idle_by_span``, ``scope_s`` over the configuration's scopes);
- ``spans``: per span ``{count, s, self_s, jit_s, jit_events}`` and
  ``queue``: ``{wait_s, admissions}`` (``trace_spans.delta``), None for
  an engine that keeps no span table;
- ``stats``: the window's change in every numeric leaf of
  ``Engine.stats()``, keyed by its dotted path;
- ``model``, ``precision``: the configuration's model and the cell's
  precision; ``contexts``: for each decode step of the window, the
  positions each lane attended to;
- ``decode_flops``, ``decode_bytes``: the counts over those steps;
  ``window_flops`` adds every prompt whose first token came in the
  window; ``scope_work``: ``decode_scopes`` over them and ``stats``, or
  None where the counts have none.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import (counts, stats, trace_reduce, trace_spans,  # noqa: E402
                   traffic as traffic_lib)
from bench.serve import Loop, compile_log, warm_shapes, warm_traffic  # noqa: E402

# Engine options a traffic file may set; every other option keeps the
# program's default (no controller, tap, speculation or tensor
# parallelism, and the platform's default matmul dispatch).
ENGINE_KEYS = ("batch_size", "cache_len", "kv_block_size", "prompt_bucket")
TRACE_DIR = ROOT / "bench-out" / "trace"     # listed in .gitignore


class Unfit(SystemExit):
    """The machine cannot run the cell: exit non-zero, print no result."""


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: Path = ROOT) -> Dict:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Unfit(f"unknown workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / conf["file"]).read_text())
    tr = json.loads((root / "bench" / "traffic"
                     / f"{w['traffic']}.json").read_text())
    traffic_lib.validate(tr)
    mine = lambda m: workload in m.get("workloads", [workload])
    return {"workload": w, "config": config, "traffic": tr, "root": root,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def check_device(chips: int) -> Dict:
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise Unfit("no accelerator: JAX's devices are CPUs")
    if len(devs) < chips:
        raise Unfit(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks_for(kind: str, root: Path = ROOT) -> Dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise Unfit(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table["devices"][kind]


def _engine(cell: Dict, params):
    from repro.models.common import ModelConfig
    from repro.serving.engine import Engine, EngineConfig
    tr = cell["traffic"]
    opts = {k: tr["engine"][k] for k in ENGINE_KEYS}
    plan = tr["plan"]
    ecfg = EngineConfig(quantize=plan != "none",
                        plan=None if plan == "none" else plan,
                        eos_token=-1, temperature=0.0, **opts)
    return Engine(params, ModelConfig(**cell["config"]["model"]), ecfg)


def _trace_start(tag: str) -> Path:
    import jax
    out = TRACE_DIR / tag
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    return out


def _trace_read(out: Path, scopes) -> Dict:
    import jax
    jax.profiler.stop_trace()
    files = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    try:
        if not files:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        tr = trace_spans.load(files[0])
        win = trace_reduce.window(tr["host"], "bench.generate")
        if win is None:
            raise RuntimeError("no bench.generate span in the trace")
        return dict(trace_reduce.reduce(tr, *win),
                    **trace_spans.reduce(tr, *win, scopes))
    finally:
        shutil.rmtree(out, ignore_errors=True)


def counts_for(cell: Dict):
    """The configuration's work counts: ``bench/work/<counts>.py`` where
    its file names one, else ``bench/counts.py``."""
    name = cell["config"].get("counts")
    if not name:
        return counts
    return _load_module(cell["root"] / "bench" / "work" / f"{name}.py",
                        f"counts_{name}")


def _sample(reqs: List[stats.Timed], t_open: float, t_close: float,
            n: int, seed: int) -> List[stats.Timed]:
    """Finished requests of the window drawn from the seed, the one with
    the most served tokens always among them; requests still in flight
    (their served prefix) fill up a window that finished too few."""
    done = [r for r in reqs if r.done and t_open <= r.finished_at <= t_close]
    rest = sorted((r for r in reqs if r.tokens and not r.done
                   and r.due < t_close), key=lambda r: -len(r.tokens))
    if not done:
        return rest[:n]
    longest = max(done, key=lambda r: len(r.tokens))
    others = [r for r in done if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    pick = [others[i] for i in rng.permutation(len(others))[:n - 1]]
    return ([longest] + pick + rest)[:n]


def check(cell: Dict, seed: int, sample: List[stats.Timed],
          control: bool) -> Dict:
    """Reference logits over each sampled prompt and its served tokens."""
    ref = _load_module(cell["root"] / "bench" / "reference"
                       / f"{cell['config']['reference']}.py", "reference")
    m = cell["config"]["model"]
    tr = cell["traffic"]
    length = tr["engine"]["cache_len"]
    params = ref.make_params(m, seed)
    gaps, control_gaps, n_tok = [], [], 0
    for r in sample:
        seq = (r.prompt + r.tokens[:-1] + [0] * length)[:length]
        rows = slice(r.prompt_len - 1, r.prompt_len - 1 + len(r.tokens))
        lg = np.asarray(ref.logits(params, seq, r.prompt_len, m,
                                   tr["precision"]))[rows]
        gaps.append(float(ref.gaps(lg, r.tokens).max()))
        n_tok += len(r.tokens)
        if control:
            lc = np.asarray(ref.logits(params, seq, r.prompt_len, m,
                                       tr["control"]))[rows]
            control_gaps.append(float(ref.gaps(lg, lc.argmax(-1)).max()))
    out = {"max_logit_gap": max(gaps) if gaps else None,
           "requests": len(sample), "tokens": n_tok}
    if control:
        out["control_max_logit_gap"] = max(control_gaps, default=None)
    return out


def _per_layer(cell: Dict, ctx: Dict) -> Dict:
    out = {}
    for spec in cell["per_layer"]:
        mod = _load_module(cell["root"] / "bench" / "metrics"
                           / f"{spec['name']}.py", f"metric_{spec['name']}")
        value = mod.read(ctx)
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


def use_compile_cache() -> None:
    """JAX's persistent cache in the checkout (the program's own helper),
    for every program, so that eager prefill pieces persist too.  The
    key leaves op metadata out, as JAX's default: with metadata in it,
    prefill programs missed the cache inside warm runs' windows and
    compiled there for seconds each (PERF.md §7).  So a trace shows the
    op names of the code that filled the cache, which in a checkout of
    its own is the code that runs."""
    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def serve_window(cell: Dict, seed: int, seconds: float, trace: bool,
                 t_process: float) -> Dict:
    """Set-up and the measured window; the engine is freed on return."""
    import jax
    device = check_device(int(cell["workload"]["chips"]))
    peaks = peaks_for(device["kind"], cell["root"])
    compiles = compile_log()
    tr, m = cell["traffic"], cell["config"]["model"]
    ref = _load_module(cell["root"] / "bench" / "reference"
                       / f"{cell['config']['reference']}.py", "reference")

    phases = [("start", time.perf_counter())]
    eng = _engine(cell, ref.make_params(m, seed))
    phases.append(("weights and engine", time.perf_counter()))
    warm_shapes(eng, tr, m["vocab"])
    phases.append(("prompt shapes", time.perf_counter()))
    loop = Loop(eng, tr, traffic_lib.stream(tr, m["vocab"], seed),
                traced=trace)
    warm_traffic(loop)
    phases.append(("mix warm-up", time.perf_counter()))
    print("setup " + ", ".join(f"{a} {t - t0:.1f} s" for (_, t0), (a, t)
                               in zip(phases, phases[1:]))
          + f"; compiles {len(compiles.compiled)}", file=sys.stderr)

    names = ("decode_iterations", "decode_seconds", "prefill_tokens",
             "prefill_iterations")
    read = lambda: dict({k: getattr(eng, k) for k in names},
                        decode_tokens=eng._decode_tokens)
    if trace:
        trace_out = _trace_start(f"{cell['workload']['name']}.{seed}")
        spans0 = trace_spans.snapshot(eng)
        stats0 = trace_spans.numeric_leaves(eng.stats())
    before = read()
    t_open = time.perf_counter()
    n_steps = len(loop.steps)
    with loop.span("bench.generate"):
        loop.run(t_open + seconds)
    t_close = time.perf_counter()
    counters = {k: v - before[k] for k, v in read().items()}
    out = {"trace": None, "spans": None, "stats": None}
    if trace:
        out.update(
            spans=trace_spans.delta(spans0, trace_spans.snapshot(eng)),
            stats=trace_spans.leaves_delta(
                stats0, trace_spans.numeric_leaves(eng.stats())),
            trace=_trace_read(trace_out, cell["config"].get("scopes", ())))
    mem = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    out.update({"device": device, "peaks": peaks, "reqs": loop.reqs,
                "steps": loop.steps[n_steps:], "t_open": t_open,
                "t_close": t_close, "setup_s": t_open - t_process,
                "counters": counters,
                "compiles": compiles.count(t_open, t_close),
                "backlog": sum(1 for r in loop.inflight.values()
                               if not r.tokens)})
    # free the engine before the reference runs
    del eng, loop
    gc.collect()
    return out


def run_cell(cell: Dict, seed: int, seconds: float, trace: bool,
             control: bool = False, t_process: float = T_PROCESS) -> Dict:
    """One run: set-up, the measured window, the reference check."""
    w = serve_window(cell, seed, seconds, trace, t_process)
    tr, m = cell["traffic"], cell["config"]["model"]
    reqs, t_open, t_close = w["reqs"], w["t_open"], w["t_close"]
    e2e = stats.end_to_end(reqs, t_open, t_close)
    due = [r for r in reqs if t_open <= r.due < t_close]
    result: Dict = {"correct": False, "attempted": len(due),
                    "failed": sum(r.failed for r in due), "metrics": {},
                    "device": w["device"]}
    if trace:
        steps, reduced, prec = w["steps"], w["trace"], tr["precision"]
        work = counts_for(cell)
        contexts = [s["contexts"] for s in steps if s["contexts"]]
        dec = [work.decode_step(m, prec, c) for c in contexts]
        first_in = [r for r in reqs
                    if r.times and t_open <= r.times[0] <= t_close]
        spans = w["spans"] or {}
        ctx = {
            "window_s": t_close - t_open, "counters": w["counters"],
            "prefill_host_s": sum(s["t1"] - s["t0"] - s["decode_s"]
                                  for s in steps if s["prefill_tokens"]),
            "compiles": w["compiles"],
            "memory_peak_bytes": w["device"]["memory_peak_bytes"],
            "trace": reduced, "peaks": w["peaks"],
            "decode_flops": sum(f for f, _ in dec),
            "decode_bytes": sum(b for _, b in dec),
            "window_flops": (sum(f for f, _ in dec)
                             + sum(work.prefill_flops(m, r.prompt_len)
                                   for r in first_in)),
            "spans": spans.get("spans"),
            "queue": ({"wait_s": spans["queue_wait_s"],
                       "admissions": spans["queue_admissions"]}
                      if spans else None),
            "stats": w["stats"], "model": m, "precision": prec,
            "contexts": contexts,
            "scope_work": (work.decode_scopes(m, prec, contexts, w["stats"])
                           if hasattr(work, "decode_scopes") else None),
        }
        print("trace scope_s " + json.dumps(reduced["scope_s"]) + " modules "
              + json.dumps({k: v for k, v in reduced["modules"].items()
                            if trace_spans.MODULE in k}), file=sys.stderr)
        result["metrics"] = _per_layer(cell, ctx)
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        values = dict(e2e, setup_s=w["setup_s"])
        for spec in cell["end_to_end"]:
            if values.get(spec["name"]) is not None:
                result["metrics"][spec["name"]] = {
                    "value": values[spec["name"]], "unit": spec["unit"]}
    result["served"] = {k: e2e[k] for k in
                        ("completed", "ttft_samples", "itl_samples")}
    sample = _sample(reqs, t_open, t_close, tr["check"]["requests"], seed)
    t_ref = time.perf_counter()
    got = check(cell, seed, sample, control)
    print(f"reference {time.perf_counter() - t_ref:.1f} s", file=sys.stderr)
    limit = tr["check"]["max_logit_gap"]
    result["correct"] = (got["max_logit_gap"] is not None
                         and got["max_logit_gap"] <= limit
                         and got["requests"] > 0)
    result["check"] = {"max_logit_gap": {"value": got["max_logit_gap"],
                                         "limit": limit},
                       "requests_checked": got["requests"],
                       "tokens_checked": got["tokens"]}
    if control:
        result["check"]["control_max_logit_gap"] = {
            "value": got["control_max_logit_gap"], "limit": limit}
    return result


def report(result: Dict) -> None:
    """Compared numbers beside their limits as the last lines of standard
    error, then the result as the last line of standard output."""
    for k, v in result["check"].items():
        if isinstance(v, dict):
            print(f"check {k} {v['value']} limit {v['limit']}",
                  file=sys.stderr)
        else:
            print(f"check {k} {v}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    use_compile_cache()
    report(run_cell(cell, args.seed, args.seconds, bool(args.trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
