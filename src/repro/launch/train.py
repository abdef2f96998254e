"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3_2_1b \
        --steps 1000 --ckpt /path/ckpt [--smoke] [--mesh dxm]

On a real multi-host slice this binary runs per host (jax.distributed
initializes from the cluster env); on this box it drives the same code on
however many devices exist.  Fault tolerance: checkpoints + SIGTERM
handling via repro.training.loop; elastic restart re-shards onto the
current mesh.
"""
from __future__ import annotations

import argparse

import jax


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--mesh", default=None,
                    help="DxM device mesh, e.g. 4x2 (default: all x 1)")
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--distributed", action="store_true",
                    help="jax.distributed.initialize() from cluster env")
    args = ap.parse_args()

    if args.distributed:
        jax.distributed.initialize()

    import repro.configs as C
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.launch.mesh import make_mesh, describe
    from repro.launch.steps import build_train_step
    from repro.training.loop import TrainLoop, TrainLoopConfig

    cfg = C.get_smoke(args.arch) if args.smoke else C.get_config(args.arch)
    n_dev = len(jax.devices())
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
    else:
        d, m = n_dev, 1
    mesh = make_mesh((d, m), ("data", "model"))
    print(describe(mesh))

    built = build_train_step(cfg, mesh, microbatches=args.microbatches or 1,
                             bf16_compute=False)
    init = built.meta["init"]
    opt = built.meta["optimizer"]
    params = init(jax.random.PRNGKey(0), cfg)
    opt_state = opt.init(params)
    print(f"{cfg.name}: "
          f"{sum(x.size for x in jax.tree_util.tree_leaves(params))/1e6:.1f}M"
          f" params, {args.steps} steps")

    data = SyntheticLM(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.global_batch,
        n_hosts=jax.process_count(), host_id=jax.process_index(),
        frontend=cfg.frontend,
        frontend_tokens=cfg.vision_tokens if cfg.frontend == "vision" else 0,
        d_model=cfg.d_model))

    loop = TrainLoop(built.fn, params, opt_state, data,
                     TrainLoopConfig(total_steps=args.steps,
                                     checkpoint_dir=args.ckpt),
                     shardings=(built.in_shardings[0],
                                built.in_shardings[1]))
    loop.install_signal_handlers()
    if loop.maybe_restore():
        print(f"resumed from step {loop.step}")
    with mesh:
        result = loop.run()
    print(f"finished at step {result['final_step']} "
          f"(preempted={result['preempted']})")


if __name__ == "__main__":
    main()
