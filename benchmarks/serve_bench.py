"""Serving benchmark gates: trace-driven replay and engine A/B checks.

Each gate replays a trace (``repro.serving.workload``) through
``repro.serving.engine.Engine``; one gate flag is required:

  * ``--replay`` — replay the identical trace under several precision
    plans (``--plan`` is repeatable, ``--slo-solve`` appends an
    SLO-solved plan) and emit a modeled-vs-measured tokens/s error
    report, optionally with the autonomous SLO controller attached
    (``--controller`` / ``--slo-frac``) — the CI ``trace-replay-gate``
    runs this mode with ``--max-rel-err`` and the controller-action
    assertions (``--expect-sheds`` / ``--expect-no-replan``).

  * ``--paged-gate`` — equal-KV-memory A/B of the fixed-slot pool vs
    the paged block pool on a prefix-heavy trace (``--prefix-len``):
    the paged engine must sustain strictly higher peak concurrency with
    per-request token-identical completions — the CI gate for the
    block-pool refactor;
  * ``--speculative`` — the baseline plan vs the same plan with a
    ``draft=`` clause: token-identical output, faster decode.

Arrivals are simulated at iteration granularity: request i is submitted
once the engine has run ``arrival_iteration`` iterations (wall-clock
free, so a trace replays deterministically on any host).  Traces are
seeded and JSON-serializable: ``--save-trace`` writes one, ``--trace``
replays a saved file bit-identically.

Run:  PYTHONPATH=src python benchmarks/serve_bench.py --replay \
          --arrival bursty --plan uniform:4 --slo-solve 1.2 \
          --controller --slo-frac 1.5 --json report.json
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from typing import Any, Dict, List


def _ensure_tp_devices(argv=None) -> None:
    """``--tp M`` on a CPU host needs M visible XLA devices, and the
    forcing flag only works BEFORE jax initializes — scan argv and set it
    here (mirrors ``repro.launch.serve``)."""
    argv = sys.argv[1:] if argv is None else argv
    tp = 1
    for i, a in enumerate(argv):
        if a == "--tp" and i + 1 < len(argv):
            tp = int(argv[i + 1])
        elif a.startswith("--tp="):
            tp = int(a.split("=", 1)[1])
    flags = os.environ.get("XLA_FLAGS", "")
    if tp > 1 and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags + f" --xla_force_host_platform_device_count={tp}").strip()


_ensure_tp_devices()

import jax  # noqa: E402  (after the device-count env fixup)

import repro.configs as C
from repro.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serving.engine import Engine, EngineConfig
from repro.serving.workload import ArrivalSpec, LengthDist, Trace, TraceSpec, generate


def build_workload(
    cfg,
    n_requests: int,
    max_new: int,
    arrival_gap: float,
    seed: int = 0,
    arrival: str = "fixed",
    burst: int = 4,
    prefix_len: int = 0,
    prompt_len: int = None,
) -> Trace:
    """The benchmark's workload as a :class:`Trace` — fully reproducible
    from ``(seed, spec)``, with ``arrival`` naming one of the generator's
    processes (fixed/poisson/bursty/diurnal) at mean gap ``arrival_gap``.
    ``prefix_len > 0`` prepends one shared system prompt to every request
    (the paged pool's prefix-sharing regime); ``prompt_len`` pins the
    per-request suffix to a constant length."""
    if prompt_len is not None:
        prompt = LengthDist(kind="constant", low=prompt_len, high=prompt_len)
    else:
        prompt = LengthDist(kind="uniform", low=4, high=13)
    spec = TraceSpec(
        seed=seed,
        n_requests=n_requests,
        vocab=cfg.vocab,
        prompt=prompt,
        output=LengthDist(kind="uniform", low=max(1, max_new // 2), high=max_new),
        arrival=ArrivalSpec(process=arrival, gap=arrival_gap, burst=burst),
        prefix_len=prefix_len,
    )
    return generate(spec)


def run_trace(params, cfg, ecfg: EngineConfig, trace: Trace) -> Dict[str, Any]:
    """Drive one engine through the trace (arrivals in engine
    iterations) and return its stats + wall-clock throughput."""
    eng = Engine(params, cfg, ecfg)
    pending = sorted(trace.requests, key=lambda r: r.arrival_iteration)
    i = 0
    t0 = time.perf_counter()
    while i < len(pending) or not eng.sched.idle():
        while i < len(pending) and pending[i].arrival_iteration <= eng.iterations:
            eng.submit(list(pending[i].prompt), max_new_tokens=pending[i].max_new_tokens)
            i += 1
        if not eng.step() and i < len(pending):
            # engine drained before the next arrival: jump to it
            eng.submit(list(pending[i].prompt), max_new_tokens=pending[i].max_new_tokens)
            i += 1
    wall = time.perf_counter() - t0
    st = eng.stats()
    st["wall_s"] = wall
    st["tok_per_s"] = st["generated_tokens"] / max(wall, 1e-9)
    st["completion_tokens"] = {str(u): c.tokens for u, c in sorted(eng.completions.items())}
    return st


# --- replay mode ----------------------------------------------------------


def _modeled_tps(params, cfg, policy, spec, batch: int, tp: int = 1, wire: int = 32) -> float:
    """Modeled decode tokens/s of a resolved plan at ``batch`` occupancy
    (the engine's ``planned_tps`` pricing, computed without building an
    engine — no quantization pass needed)."""
    from repro import planning

    units = planning.policy_units(params, policy)
    fixed = planning.unquantized_bytes(params, policy)
    kw: Dict[str, Any] = {"batch": batch, "prt": spec.prt, "nbw": spec.nbw}
    if spec.calibration is not None:
        kw["machine"] = planning.machine_from_json(spec.calibration)
        disp = planning.dispatch_from_json(spec.calibration)
        if disp is not None:
            kw["dispatch_cycles"] = disp
    if tp > 1:
        kw.update(tp=tp, wire_bits=wire, allreduce_elems=planning.tp_allreduce_elems(cfg))
    cost = planning.DecodeCostModel(**kw)
    secs = cost.iteration_seconds(
        cost.cycles(units), cost.qbytes(units, policy.group_size) + fixed
    )
    return batch / max(secs, 1e-30)


def _resolve_plans(args, params, cfg) -> List[Dict[str, Any]]:
    """CLI plan args -> [{label, spec, policy, modeled_tps}], resolving
    each once (auto plans run the Planner here, not per engine build)."""
    from repro import planning
    from repro.models.sail_linear import QuantPolicy

    base = QuantPolicy(bits=args.ql, group_size=32, min_size=1024)
    out: List[Dict[str, Any]] = []
    for arg in args.plan or ["uniform:%d" % args.ql]:
        plan = planning.plan_from_arg(arg)
        result = planning.resolve_plan(plan, params, cfg, base=base)
        out.append(
            {
                "label": arg,
                "spec": result.spec,
                "policy": result.policy,
                "modeled_tps": _modeled_tps(params, cfg, result.policy, result.spec, args.batch),
            }
        )
    if args.slo_solve is not None:
        # SLO-solved plan: target quoted against the baseline plan's own
        # modeled capacity, so the solve is self-referencing (no
        # hardcoded tokens/s that would rot with the cost model)
        target = args.slo_solve * out[0]["modeled_tps"]
        slo = planning.Slo(target, batch=args.batch)
        plan = planning.PlanSpec(
            mode="auto", weight_bits=args.ql, act_bits=8, prt="measured", quant_kv=True
        )
        result = planning.resolve_plan(plan, params, cfg, base=base, slo=slo)
        out.append(
            {
                "label": f"slo-solve:{args.slo_solve:g}x",
                "spec": result.spec,
                "policy": result.policy,
                "modeled_tps": _modeled_tps(params, cfg, result.policy, result.spec, args.batch),
            }
        )
    return out


def _replay(args, params, cfg, trace: Trace) -> Dict[str, Any]:
    """Replay the trace under every plan; fit one measured/modeled scale
    across plans (geometric mean — the host is not the modeled SAIL
    machine) and report each plan's residual relative error."""
    plans = _resolve_plans(args, params, cfg)
    entries: List[Dict[str, Any]] = []
    for p in plans:
        slo = args.slo_frac * p["modeled_tps"] if args.slo_frac is not None else None
        ecfg = EngineConfig(
            batch_size=args.batch,
            cache_len=args.cache_len,
            quantize=True,
            ql=args.ql,
            group_size=32,
            quant_kv=True,
            plan=p["spec"],
            slo=slo,
            controller=args.controller or None,
            tap_capacity=args.tap if args.controller else 0,
            prefill_budget=args.prefill_budget,
            kv_block_size=args.block_size if args.paged else None,
            kv_pool_blocks=args.pool_blocks,
            kv_budget_bytes=args.kv_budget,
        )
        st = run_trace(params, cfg, ecfg, trace)
        tokens = st.pop("completion_tokens")
        if args.verify_determinism:
            st2 = run_trace(params, cfg, ecfg, trace)
            if st2.pop("completion_tokens") != tokens:
                raise SystemExit(f"FAIL: plan {p['label']} replay was not token-identical")
        entries.append(
            {
                "plan": p["label"],
                "plan_hash": st["plan_hash"],
                "plan_mode": st["plan_mode"],
                "slo_tps": slo,
                # occupancy-matched: each iteration priced at its true
                # occupancy, so controller caps don't read as model error
                "modeled_tps": st["modeled_run_tps"] or st["planned_tps"],
                "planned_tps": st["planned_tps"],
                "measured_tps": st["measured_tps"],
                "wall_s": st["wall_s"],
                "generated_tokens": st["generated_tokens"],
                "requests": st["requests"],
                "decode_iterations": st["decode_iterations"],
                "replan_count": st["replan_count"],
                "controller": st["controller"],
                # paged-pool observability (None on the slot pool): peak
                # blocks in use, shared-block hit ratio, preemptions
                "peak_active": st["peak_active"],
                "block_pool": st["block_pool"],
            }
        )
    ratios = [e["measured_tps"] / e["modeled_tps"] for e in entries]
    scale = math.exp(sum(math.log(r) for r in ratios) / len(ratios))
    for e, r in zip(entries, ratios):
        e["measured_over_modeled"] = r
        e["rel_err"] = abs(r / scale - 1.0)
    return {
        "trace": {
            "hash": trace.trace_hash,
            "requests": len(trace.requests),
            "prompt_tokens": trace.total_prompt_tokens,
            "new_tokens": trace.total_new_tokens,
            "spec": trace.spec.to_json(),
        },
        "scale": scale,
        "max_rel_err": max(e["rel_err"] for e in entries),
        "bound": args.max_rel_err,
        "slo_frac": args.slo_frac,
        "plans": entries,
    }


def _gate(args, report: Dict[str, Any]) -> None:
    """CI assertions: modeled-vs-measured error bound + controller
    behavior (sheds under SLO pressure, no replans on steady traffic)."""
    failures: List[str] = []
    if args.max_rel_err is not None and report["max_rel_err"] > args.max_rel_err:
        failures.append(
            f"modeled-vs-measured rel err {report['max_rel_err']:.3f} "
            f"exceeds bound {args.max_rel_err:.3f}"
        )
    if args.expect_sheds:
        acted = any(
            e["controller"] is not None
            and (e["controller"]["shed"] > 0 or e["controller"]["shrink"] > 0)
            for e in report["plans"]
        )
        if not acted:
            failures.append("expected >= 1 shed/shrink under SLO pressure, controller never acted")
    if args.expect_no_replan:
        for e in report["plans"]:
            c = e["controller"]
            if c is not None and (c["replan"] > 0 or c["resolve"] > 0):
                failures.append(
                    f"plan {e['plan']}: {c['replan']} replans / {c['resolve']} "
                    "resolves on a trace that expected none"
                )
    if failures:
        raise SystemExit("FAIL: " + "; ".join(failures))


# --- paged gate -----------------------------------------------------------


def _paged_gate(args, params, cfg, trace: Trace) -> Dict[str, Any]:
    """Equal-memory A/B: fixed-slot pool vs paged block pool on a
    prefix-heavy trace.

    Both engines get the SAME KV byte budget — ``--gate-slots`` full
    ``cache_len`` slots, which the paged side receives as the equivalent
    block count (``cache_len`` must divide evenly into blocks so the
    budgets match exactly).  The gate asserts the paged engine (a) held
    strictly more requests in flight at its peak and (b) produced
    token-identical completions per request — prefix sharing buys
    concurrency, never output drift."""
    from repro import planning

    bs = args.block_size
    clen = args.cache_len
    if clen % bs:
        raise SystemExit(f"--cache-len {clen} must be a multiple of --block-size {bs}")
    mbs = clen // bs
    tok_bytes = planning.kv_token_bytes(lm.n_scan_blocks(cfg), cfg.n_kv, cfg.head_dim, 8)
    budget = args.gate_slots * clen * tok_bytes

    common = dict(
        cache_len=clen,
        quantize=True,
        ql=args.ql,
        group_size=32,
        quant_kv=True,
        prefill_budget=args.prefill_budget,
    )
    slot = run_trace(
        params, cfg, EngineConfig(batch_size=args.gate_slots, **common), trace
    )
    paged = run_trace(
        params,
        cfg,
        EngineConfig(
            batch_size=args.batch,
            kv_block_size=bs,
            kv_pool_blocks=args.gate_slots * mbs,
            **common,
        ),
        trace,
    )
    slot_tokens = slot.pop("completion_tokens")
    paged_tokens = paged.pop("completion_tokens")
    identical = slot_tokens == paged_tokens
    report = {
        "trace": {
            "hash": trace.trace_hash,
            "requests": len(trace.requests),
            "prefix_len": trace.spec.prefix_len,
            "spec": trace.spec.to_json(),
        },
        "kv_budget_bytes": budget,
        "slot": {
            "batch_size": args.gate_slots,
            "peak_active": slot["peak_active"],
            "iterations": slot["iterations"],
            "mean_ttft_s": slot["mean_ttft_s"],
        },
        "paged": {
            "batch_size": args.batch,
            "pool_blocks": args.gate_slots * mbs,
            "block_size": bs,
            "peak_active": paged["peak_active"],
            "iterations": paged["iterations"],
            "mean_ttft_s": paged["mean_ttft_s"],
            "block_pool": paged["block_pool"],
        },
        "token_identical": identical,
    }
    print(
        f"equal KV budget {budget} B ({args.gate_slots} x {clen}-token slots"
        f" == {args.gate_slots * mbs} x {bs}-token blocks):"
    )
    print(
        f"  slot  pool: peak {slot['peak_active']} concurrent, "
        f"{slot['iterations']} iterations"
    )
    bp = paged["block_pool"]
    print(
        f"  paged pool: peak {paged['peak_active']} concurrent, "
        f"{paged['iterations']} iterations, shared ratio "
        f"{bp['shared_ratio']:.2f}, {bp['preemptions']} preemptions"
    )
    print(f"  completions token-identical: {identical}")
    failures = []
    if paged["peak_active"] <= slot["peak_active"]:
        failures.append(
            f"paged peak concurrency {paged['peak_active']} did not beat "
            f"the slot pool's {slot['peak_active']} at equal memory"
        )
    if not identical:
        failures.append("paged completions diverged from the slot pool's")
    if failures:
        raise SystemExit("FAIL: " + "; ".join(failures))
    return report


# --- speculative gate -----------------------------------------------------


def _speculative_gate(args, params, cfg, trace: Trace) -> Dict[str, Any]:
    """Greedy self-speculative A/B: one weight tree, two plans.

    Replays the identical trace through the baseline plan and through the
    same plan with a ``draft=`` clause, greedy both times.  The gate
    asserts (a) token-identical completions per request — greedy
    speculative decoding is exact, the draft/verify machinery may never
    change output — and (b) measured decode tokens/s at least
    ``--spec-speedup`` x the baseline's.  Each side runs twice and the
    second run is timed: the first pays the jit compiles (the baseline
    and speculative paths compile different kernels) and doubles as a
    determinism check.

    The default draft is the SAME precision as the baseline plan
    (``q8a8:k8`` under ``uniform:8a8``): on this op-count-bound reference
    backend a lower-bit draft step costs exactly what a full step costs,
    so the measured win isolates what IS measurable on the host — one
    fused k-token draft dispatch plus one batched verify dispatch
    replacing k+1 single-token iterations, with per-position acceptance
    exactly 1.  The bit-gap economics (fewer draft bytes vs acceptance
    loss) are SAIL-hardware quantities; the DecodeCostModel prices them
    and the planner's ``draft=auto`` solve arbitrates — pass a low-bit
    ``--spec-draft`` (e.g. ``q4a8:k3``) to exercise the lossy-draft
    accept/rollback path, which must still be token-identical.

    Saturate the engine for a stable measurement: arrivals are indexed
    by engine *iterations* and one speculative round is one iteration,
    so a staggered trace starves the speculative side's batch (run with
    ``--arrival-gap 0``)."""
    base_label = (args.plan or ["uniform:8a8"])[0]
    spec_label = f"{base_label},draft={args.spec_draft}"
    common = dict(
        batch_size=args.batch,
        cache_len=args.cache_len,
        quantize=True,
        group_size=32,
        min_size=1024,
        quant_kv=False,
        prefill_budget=args.prefill_budget,
    )

    def timed(label):
        warm = run_trace(params, cfg, EngineConfig(plan=label, **common), trace)
        st = run_trace(params, cfg, EngineConfig(plan=label, **common), trace)
        if warm["completion_tokens"] != st["completion_tokens"]:
            raise SystemExit(f"FAIL: plan {label} replay was not token-identical")
        return st

    base = timed(base_label)
    spec = timed(spec_label)
    base_tokens = base.pop("completion_tokens")
    spec_tokens = spec.pop("completion_tokens")
    identical = base_tokens == spec_tokens
    speedup = spec["measured_tps"] / max(base["measured_tps"], 1e-9)
    sstat = spec["speculative"]
    report = {
        "trace": {
            "hash": trace.trace_hash,
            "requests": len(trace.requests),
            "spec": trace.spec.to_json(),
        },
        "baseline": {
            "plan": base_label,
            "measured_tps": base["measured_tps"],
            "decode_iterations": base["decode_iterations"],
            "generated_tokens": base["generated_tokens"],
        },
        "speculative": {
            "plan": spec_label,
            "measured_tps": spec["measured_tps"],
            "decode_iterations": spec["decode_iterations"],
            "generated_tokens": spec["generated_tokens"],
            "rounds": sstat["rounds"],
            "acceptance_rate": sstat["acceptance_rate"],
            "expected_tokens_per_round": sstat["expected_tokens_per_round"],
        },
        "token_identical": identical,
        "speedup": speedup,
        "bound": args.spec_speedup,
    }
    print(
        f"speculative gate ({spec_label} vs {base_label}): "
        f"{spec['measured_tps']:.1f} vs {base['measured_tps']:.1f} decode tok/s "
        f"= {speedup:.2f}x (bound {args.spec_speedup:g}x)"
    )
    print(
        f"  {sstat['rounds']} rounds, acceptance {sstat['acceptance_rate']:.3f}, "
        f"{spec['decode_iterations']}/{base['decode_iterations']} decode iterations, "
        f"token-identical: {identical}"
    )
    failures = []
    if not identical:
        failures.append("greedy speculative completions diverged from the baseline's")
    if speedup < args.spec_speedup:
        failures.append(
            f"measured speculative speedup {speedup:.2f}x below bound {args.spec_speedup:g}x"
        )
    if failures:
        raise SystemExit("FAIL: " + "; ".join(failures))
    return report


# --- tensor-parallel gate -------------------------------------------------


def _tp_gate(args, params, cfg, trace: Trace) -> Dict[str, Any]:
    """Greedy tp=M vs tp=1 A/B on the identical trace.

    The same plan serves the same trace single-device and sharded over
    ``--tp`` model-parallel shards; the gate asserts token-identical
    completions per request — sharding the quantized tree may buy
    throughput, never output drift (wire=32; the int8 wire is lossy by
    design and has its own bounded-error property test).  The report
    carries the engine's tp stats plus a per-shard modeled timing split
    (compute / DRAM / wire) — the CI artifact.
    """
    from repro import planning
    from repro.models.sail_linear import QuantPolicy

    label = (args.plan or ["uniform:%d" % args.ql])[0]
    common = dict(
        batch_size=args.batch,
        cache_len=args.cache_len,
        quantize=True,
        ql=args.ql,
        group_size=32,
        quant_kv=True,
        plan=label,
        prefill_budget=args.prefill_budget,
        kv_block_size=args.block_size if args.paged else None,
        kv_pool_blocks=args.pool_blocks,
    )
    base = run_trace(params, cfg, EngineConfig(tp=1, **common), trace)
    shard = run_trace(params, cfg, EngineConfig(tp=args.tp, wire=args.wire, **common), trace)
    base_tokens = base.pop("completion_tokens")
    shard_tokens = shard.pop("completion_tokens")
    identical = base_tokens == shard_tokens

    # per-shard modeled split: each shard runs 1/tp of the lookups and
    # streams 1/tp of the quantized bytes; the wire term is the ring
    # all-reduce every shard pays in full
    base_q = QuantPolicy(bits=args.ql, group_size=32, min_size=1024)
    spec_obj = planning.as_plan(label)
    if not spec_obj.solved:
        spec_obj = planning.resolve_plan(spec_obj, params, cfg, base=base_q).spec
    policy = spec_obj.to_policy(base_q)
    units = planning.policy_units(params, policy)
    fixed = planning.unquantized_bytes(params, policy)
    cost = planning.DecodeCostModel(
        batch=args.batch,
        prt=spec_obj.prt,
        nbw=spec_obj.nbw,
        tp=args.tp,
        wire_bits=args.wire,
        allreduce_elems=planning.tp_allreduce_elems(cfg),
    )
    cycles = cost.cycles(units)
    total = cost.qbytes(units, policy.group_size) + fixed
    per_shard = [
        {
            "shard": i,
            "modeled_compute_s": cost.t_compute(cycles),
            "modeled_dram_s": cost.t_dram(total),
            "modeled_wire_s": cost.t_wire(args.batch),
        }
        for i in range(args.tp)
    ]
    report = {
        "trace": {
            "hash": trace.trace_hash,
            "requests": len(trace.requests),
            "spec": trace.spec.to_json(),
        },
        "plan": label,
        "pool": "paged" if args.paged else "ring",
        "tp1": {
            "measured_tps": base["measured_tps"],
            "decode_iterations": base["decode_iterations"],
            "generated_tokens": base["generated_tokens"],
        },
        "tp": {
            "shards": args.tp,
            "wire_bits": args.wire,
            "measured_tps": shard["measured_tps"],
            "decode_iterations": shard["decode_iterations"],
            "generated_tokens": shard["generated_tokens"],
            "stats": shard["tp"],
            "per_shard": per_shard,
        },
        "token_identical": identical,
    }
    print(
        f"tp gate ({label}, {report['pool']} pool): tp={args.tp} wire={args.wire} "
        f"vs tp=1 on trace {trace.trace_hash}"
    )
    print(
        f"  tp=1: {base['measured_tps']:.1f} tok/s over {base['decode_iterations']} iterations; "
        f"tp={args.tp}: {shard['measured_tps']:.1f} tok/s over {shard['decode_iterations']}"
    )
    st = shard["tp"]
    print(
        f"  all-reduce {st['allreduce_bytes_per_iter']} B/iter, modeled wire share "
        f"{st['modeled_wire_share']:.3f}" if st["modeled_wire_share"] is not None
        else f"  all-reduce {st['allreduce_bytes_per_iter']} B/iter"
    )
    print(f"  completions token-identical: {identical}")
    if not identical:
        raise SystemExit(f"FAIL: tp={args.tp} completions diverged from tp=1 on the same trace")
    return report


# --- CLI ------------------------------------------------------------------


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinymistral_248m")
    ap.add_argument("--ql", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0, help="trace generator seed")
    ap.add_argument(
        "--arrival",
        default="fixed",
        choices=["fixed", "poisson", "bursty", "diurnal"],
        help="arrival process (mean gap --arrival-gap iterations)",
    )
    ap.add_argument(
        "--arrival-gap",
        type=float,
        default=3,
        help="mean iterations between request arrivals",
    )
    ap.add_argument("--burst", type=int, default=4, help="bursty: arrivals per burst")
    ap.add_argument("--prefill-budget", type=int, default=64)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument(
        "--trace",
        default=None,
        metavar="PATH",
        help="replay this saved trace.json instead of generating one",
    )
    ap.add_argument("--save-trace", default=None, metavar="PATH", help="write the trace JSON here")
    ap.add_argument(
        "--plan",
        action="append",
        default=None,
        help="precision plan (grammar string or plan.json path); "
        "repeatable in --replay mode, the first one serves elsewhere",
    )
    ap.add_argument("--json", default=None, help="write the stats/report JSON here")
    # replay mode
    ap.add_argument(
        "--replay",
        action="store_true",
        help="replay the trace under each --plan and report modeled-vs-measured error",
    )
    ap.add_argument(
        "--slo-solve",
        type=float,
        default=None,
        metavar="FRAC",
        help="replay: append an SLO-solved plan targeting FRAC x the "
        "baseline plan's modeled tokens/s",
    )
    ap.add_argument(
        "--controller",
        action="store_true",
        help="replay: attach the autonomous SLO controller to each engine",
    )
    ap.add_argument(
        "--slo-frac",
        type=float,
        default=None,
        metavar="FRAC",
        help="replay: serve each plan under an SLO of FRAC x its own "
        "modeled tokens/s (FRAC > 1 forces shed/shrink pressure)",
    )
    ap.add_argument("--tap", type=int, default=64, help="replay: ActivationTap rows (controller)")
    ap.add_argument(
        "--max-rel-err",
        type=float,
        default=None,
        help="gate: fail when any plan's scale-fitted modeled-vs-measured "
        "relative error exceeds this",
    )
    ap.add_argument(
        "--expect-sheds",
        action="store_true",
        help="gate: fail unless the controller shed/shrank at least once",
    )
    ap.add_argument(
        "--expect-no-replan",
        action="store_true",
        help="gate: fail if the controller replanned/resolved",
    )
    ap.add_argument(
        "--verify-determinism",
        action="store_true",
        help="replay each plan twice and require token-identical output",
    )
    # paged KV pool
    ap.add_argument(
        "--prefix-len",
        type=int,
        default=0,
        help="shared system-prompt tokens prepended to every request",
    )
    ap.add_argument(
        "--prompt-len",
        type=int,
        default=None,
        help="pin every request's (post-prefix) prompt to this length",
    )
    ap.add_argument(
        "--paged",
        action="store_true",
        help="replay: serve from the paged block pool instead of slots",
    )
    ap.add_argument("--block-size", type=int, default=16, help="paged: tokens per KV block")
    ap.add_argument("--pool-blocks", type=int, default=None, help="paged: pool size in blocks")
    ap.add_argument(
        "--kv-budget",
        type=int,
        default=None,
        metavar="BYTES",
        help="paged: size the pool from a KV byte budget",
    )
    ap.add_argument(
        "--paged-gate",
        action="store_true",
        help="equal-memory slot-vs-paged A/B gate: the paged pool must "
        "sustain strictly higher peak concurrency with token-identical "
        "output (prefix-heavy traces; see --prefix-len/--gate-slots)",
    )
    ap.add_argument(
        "--gate-slots",
        type=int,
        default=3,
        help="paged gate: KV budget quoted as this many full cache_len slots",
    )
    # tensor-parallel serving
    ap.add_argument(
        "--tp",
        type=int,
        default=1,
        help="with --replay: tp=M vs tp=1 A/B gate on the same trace — "
        "token-identity required (repro.serving.distributed); forces M "
        "host devices on CPU automatically",
    )
    ap.add_argument(
        "--wire",
        type=int,
        default=32,
        choices=(8, 32),
        help="tp gate: all-reduce precision (32 exact, 8 compressed)",
    )
    # self-speculative decoding
    ap.add_argument(
        "--speculative",
        action="store_true",
        help="A/B gate: the baseline plan vs the same plan with a draft= "
        "clause must be token-identical (greedy) and at least "
        "--spec-speedup x faster in measured decode tokens/s",
    )
    ap.add_argument(
        "--spec-draft",
        default="q8a8:k8",
        help="speculative gate: the draft= clause (q<b>[a<ab>]:k<k>); "
        "the same-precision default isolates round amortization, a "
        "low-bit value exercises lossy-draft accept/rollback",
    )
    ap.add_argument(
        "--spec-speedup",
        type=float,
        default=1.2,
        help="speculative gate: minimum measured decode tokens/s ratio",
    )
    args = ap.parse_args()
    if not (args.replay or args.paged_gate or args.speculative):
        ap.error("choose a gate: --replay, --paged-gate or --speculative")

    cfg = C.get_smoke(args.arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    if args.trace is not None:
        trace = Trace.load(args.trace)
    else:
        trace = build_workload(
            cfg,
            args.requests,
            args.max_new,
            args.arrival_gap,
            seed=args.seed,
            arrival=args.arrival,
            burst=args.burst,
            prefix_len=args.prefix_len,
            prompt_len=args.prompt_len,
        )
    if args.save_trace:
        trace.save(args.save_trace)
        print(f"wrote {args.save_trace}")
    print(
        f"{cfg.name}: {len(trace.requests)} requests "
        f"({trace.spec.arrival.process} arrivals, trace {trace.trace_hash}, "
        f"{trace.total_prompt_tokens} prompt tokens, "
        f"<= {trace.total_new_tokens} new), pool of {args.batch} slots"
    )

    if args.paged_gate:
        report = _paged_gate(args, params, cfg, trace)
        if args.json:
            with open(args.json, "w") as f:
                json.dump(report, f, indent=2)
            print(f"wrote {args.json}")
        return

    if args.speculative:
        report = _speculative_gate(args, params, cfg, trace)
        if args.json:
            with open(args.json, "w") as f:
                json.dump(report, f, indent=2)
            print(f"wrote {args.json}")
        return

    if args.replay and args.tp > 1:
        report = _tp_gate(args, params, cfg, trace)
        if args.json:
            with open(args.json, "w") as f:
                json.dump(report, f, indent=2)
            print(f"wrote {args.json}")
        return

    if args.replay:
        report = _replay(args, params, cfg, trace)
        hdr = (
            f"{'plan':<18} {'modeled':>12} {'measured':>10} {'ratio':>10} "
            f"{'rel err':>8} {'shed':>5} {'replan':>7}"
        )
        print(hdr)
        print("-" * len(hdr))
        for e in report["plans"]:
            c = e["controller"] or {}
            print(
                f"{e['plan']:<18} {e['modeled_tps']:>12.0f} "
                f"{e['measured_tps']:>10.1f} {e['measured_over_modeled']:>10.2e} "
                f"{e['rel_err']:>8.3f} {c.get('shed', 0):>5} "
                f"{c.get('replan', 0) + c.get('resolve', 0):>7}"
            )
        print(
            f"measured/modeled scale {report['scale']:.3e} (geomean), "
            f"max residual rel err {report['max_rel_err']:.3f}"
            + (f" (bound {args.max_rel_err})" if args.max_rel_err is not None else "")
        )
        if args.json:
            with open(args.json, "w") as f:
                json.dump(report, f, indent=2)
            print(f"wrote {args.json}")
        _gate(args, report)


if __name__ == "__main__":
    main()
