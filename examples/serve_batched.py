"""Batched serving with the SAIL quantized path (tensor-level scheduling).

Quantizes a model to ql bits, serves prompts through the
continuous-batching engine (weights streamed once per iteration, reused
by all active users — the paper's Sec. III-A — with finished slots
back-filled at iteration granularity), and reports measured CPU
throughput plus the calibrated SAIL machine model's projection for the
same workload on the paper's hardware.

Run:  PYTHONPATH=src python examples/serve_batched.py --ql 4 --batch 8
"""
import argparse
import time

import jax
import numpy as np

import repro.configs as C
from repro.core import cost_model as cm
from repro.models import lm
from repro.serving.engine import Engine, EngineConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinymistral_248m")
    ap.add_argument("--ql", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--plan", default=None,
                    help="precision plan, e.g. rules:mlp=3,attn=5 or "
                         "auto:q4a8,prt=measured, or a plan.json path "
                         "(see repro.planning)")
    ap.add_argument("--full", action="store_true",
                    help="use the full config instead of smoke (slow)")
    args = ap.parse_args()

    cfg = C.get_config(args.arch) if args.full else C.get_smoke(args.arch)
    key = jax.random.PRNGKey(0)
    params = lm.init_params(key, cfg)

    from repro.planning import plan_from_arg
    plan = plan_from_arg(args.plan) if args.plan is not None else None
    engine = Engine(params, cfg, EngineConfig(
        batch_size=args.batch, cache_len=256, quantize=True, ql=args.ql,
        group_size=32, quant_kv=True, plan=plan))
    wdesc = (f"mixed ({args.plan})"
             if engine.stats()["mixed_precision"] else f"Q{args.ql}")
    print(f"serving {cfg.name}: weights {wdesc}, "
          f"compression {engine.compression:.2f}x, int8 KV cache")

    rng = np.random.default_rng(0)
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, size=rng.integers(4, 12)).tolist()
        engine.submit(prompt, max_new_tokens=args.max_new)

    t0 = time.time()
    completions = engine.run()
    dt = time.time() - t0
    st = engine.stats()
    print(f"served {st['requests']} requests / "
          f"{st['generated_tokens']} tokens in {dt:.1f}s "
          f"({st['generated_tokens']/dt:.2f} tok/s measured on this CPU, "
          f"{st['iterations']} model iterations, "
          f"mean TTFT {st['mean_ttft_s']:.2f}s)")
    for c in completions[:3]:
        print(f"  req {c.uid}: {len(c.tokens)} tokens, "
              f"latency {c.latency_s:.2f}s, first tokens {c.tokens[:8]}")

    # SAIL machine-model projection for the same (model-size, ql, batch)
    model = cm.ModelSpec("arch", sum(
        x.size for x in jax.tree_util.tree_leaves(params)),
        cfg.d_model, cfg.n_layers, cfg.d_ff or cfg.d_model * 4)
    proj = cm.sail_tokens_per_second(model, args.ql, threads=16,
                                     batch=args.batch)
    arm = cm.arm_tokens_per_second(model, args.ql, threads=16,
                                   batch=args.batch)
    print(f"SAIL machine-model projection @16T/batch{args.batch}: "
          f"{proj:.1f} tok/s (ARM CPU baseline {arm:.1f} -> "
          f"{proj/arm:.1f}x)")


if __name__ == "__main__":
    main()
