"""Production serving launcher (SAIL quantized path).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_0_6b --smoke \
        --ql 4 --batch 8 --requests 16

Quantizes weights to ``--ql`` bits (QTensor storage), int8 KV cache,
continuous batching over a fixed pool of ``--batch`` KV-cache slots (one
model iteration serves every active user — the paper's tensor-level
scheduling).

Precision planning (``repro.planning``):

    # serve a plan: grammar string or solved plan.json
    ... --plan "auto:q4a8,prt=measured,maxseg=4" --save-plan plan.json
    ... --plan plan.json          # reuse: no recalibration at startup

    # SLO-driven: derive the cycle+DRAM budgets from a target tokens/s
    ... --slo 80 --tap 512        # tap live traffic for later replans
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def _ensure_tp_devices(argv=None) -> None:
    """``--tp M`` on a CPU host needs M visible XLA devices, and the
    forcing flag only works BEFORE jax initializes — scan argv and set it
    here so ``python -m repro.launch.serve --tp 4`` just works.  Real
    multi-device backends (and an explicit user XLA_FLAGS) are left
    alone."""
    argv = sys.argv[1:] if argv is None else argv
    tp = 1
    for i, a in enumerate(argv):
        if a == "--tp" and i + 1 < len(argv):
            tp = int(argv[i + 1])
        elif a.startswith("--tp="):
            tp = int(a.split("=", 1)[1])
    flags = os.environ.get("XLA_FLAGS", "")
    if tp > 1 and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={tp}").strip()


_ensure_tp_devices()

import jax  # noqa: E402  (after the device-count env fixup)
import numpy as np  # noqa: E402


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ql", type=int, default=4)
    ap.add_argument("--group-size", type=int, default=None,
                    help="quantization group size (default "
                         "min(128, d_model)); under --tp the per-matrix "
                         "group count must divide the shard count")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=512)
    ap.add_argument("--no-quant-kv", action="store_true")
    ap.add_argument("--plan", default=None,
                    help="precision plan: a grammar string "
                         "(uniform:<b>[a<ab>] | rules:<regex>=<b>[a<ab>],"
                         "... | auto:q<b>[a<ab>][,prt=...][,maxseg=<n>]"
                         "[,slo=<tps>] | auto:<f>bpw) or a path to a "
                         "plan.json written by --save-plan (solved plans "
                         "serve without recalibration)")
    ap.add_argument("--slo", type=float, default=None,
                    help="target decode tokens/s at --batch: auto plans "
                         "derive their cycle AND DRAM-byte budgets from "
                         "this instead of a fixed constant (implies "
                         "auto:q<ql>a8,prt=measured when --plan is "
                         "omitted)")
    ap.add_argument("--save-plan", default=None,
                    help="write the engine's (solved) plan JSON here")
    ap.add_argument("--tap", type=int, default=0, metavar="ROWS",
                    help="capture per-layer decode activations into an "
                         "ActivationTap of this capacity (enables online "
                         "PRT recalibration via Engine.replan)")
    ap.add_argument("--controller", action="store_true",
                    help="attach the autonomous SLO controller "
                         "(repro.serving.control.SloController): sheds/"
                         "shrinks occupancy against --slo and gates "
                         "replans on measured-vs-modeled drift")
    ap.add_argument("--deadband", type=float, default=None,
                    help="controller: |anchored drift| tolerated without "
                         "action (default 0.25)")
    ap.add_argument("--cooldown", type=int, default=None,
                    help="controller: decode iterations between actions "
                         "(default 32)")
    ap.add_argument("--check-every", type=int, default=None,
                    help="controller: decode iterations between drift "
                         "checks (default 8)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shard count: shard the "
                         "quantized weight tree over a (1, M) mesh "
                         "(repro.serving.distributed).  On CPU the "
                         "launcher forces M host devices automatically; "
                         "a plan carrying tp= overrides this knob")
    ap.add_argument("--wire", type=int, default=32, choices=(8, 32),
                    help="TP all-reduce precision: 32 exact, 8 "
                         "compressed int8+scale partial sums")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="max new prefill tokens admitted per iteration")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they are generated")
    args = ap.parse_args()

    import repro.configs as C
    from repro.models import lm
    from repro.planning import plan_from_arg
    from repro.serving.engine import Engine, EngineConfig

    cfg = C.get_smoke(args.arch) if args.smoke else C.get_config(args.arch)
    if cfg.family == "encdec":
        raise SystemExit("use a decoder-only arch for the LM server")
    plan = plan_from_arg(args.plan) if args.plan is not None else None
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    controller = None
    if args.controller:
        knobs = {k: v for k, v in (("deadband", args.deadband),
                                   ("cooldown", args.cooldown),
                                   ("check_every", args.check_every))
                 if v is not None}
        controller = knobs or True
    eng = Engine(params, cfg, EngineConfig(
        batch_size=args.batch, cache_len=args.cache_len, quantize=True,
        ql=args.ql,
        group_size=(args.group_size if args.group_size is not None
                    else min(128, cfg.d_model)),
        quant_kv=not args.no_quant_kv,
        plan=plan, slo=args.slo, tap_capacity=args.tap,
        controller=controller, prefill_budget=args.prefill_budget,
        tp=args.tp, wire=args.wire))
    st = eng.stats()
    quant_desc = (f"mixed-precision plan {st['plan_hash']}"
                  if st["mixed_precision"]
                  else f"Q{args.ql} (plan {st['plan_hash']})")
    tp_desc = ""
    if st["tp"] is not None:
        tp_desc = (f", tp={st['tp']['shards']} "
                   f"(wire={st['tp']['wire_bits']})")
    print(f"{cfg.name}: {quant_desc} weights "
          f"({eng.compression:.2f}x compression), "
          f"{'int8' if not args.no_quant_kv else 'f32'} KV, "
          f"continuous scheduling{tp_desc}")
    if args.save_plan and eng.plan is not None:
        eng.plan.save(args.save_plan)
        print(f"wrote plan {eng.plan.spec_hash} to {args.save_plan}")

    on_token = None
    if args.stream:
        on_token = lambda uid, tok: print(f"  [uid {uid}] {tok}")
    rng = np.random.default_rng(0)
    for _ in range(args.requests):
        n = int(rng.integers(4, 16))
        eng.submit(rng.integers(0, cfg.vocab, size=n).tolist(),
                   max_new_tokens=args.max_new, on_token=on_token)
    t0 = time.time()
    eng.run()
    dt = time.time() - t0
    st = eng.stats()
    print(f"{st['requests']} requests, {st['generated_tokens']} tokens, "
          f"{st['generated_tokens']/dt:.2f} tok/s, "
          f"mean latency {st['mean_latency_s']:.2f}s "
          f"(p99 {st['p99_latency_s']:.2f}s), "
          f"mean TTFT {st['mean_ttft_s']:.2f}s, "
          f"{st['iterations']} model iterations "
          f"({st['prefill_iterations']} prefill / "
          f"{st['decode_iterations']} decode, "
          f"{st['prefill_tokens']} prompt tokens)")
    if st["measured_tps"] is not None and st["planned_tps"]:
        print(f"decode: measured {st['measured_tps']:.1f} tok/s vs "
              f"modeled {st['planned_tps']:.0f} tok/s at the full pool "
              f"(raw drift {st['drift']:+.3f} — absolute value is "
              f"meaningful once the plan carries host calibration)")
    if st["controller"] is not None:
        c = st["controller"]
        print(f"controller: batch cap {c['batch_cap']}, "
              f"{c['checks']} drift checks, "
              f"shed {c['shed']} / shrink {c['shrink']} / "
              f"replan {c['replan']} / resolve {c['resolve']}")
    if args.tap:
        print(f"tap: {st['tapped_rows']} activation rows captured across "
              f"{eng.tap.n_layers} layers (Engine.replan() recalibrates "
              f"measured PRT discounts from them)")


if __name__ == "__main__":
    main()
