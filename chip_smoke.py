#!/usr/bin/env python3
"""On-chip smoke test of the quantized serving path on a TPU.

    python3 chip_smoke.py            # one chip: device, kernel, engine
    python3 chip_smoke.py --tp 4     # four chips: engine at tp=4 and tp=1

Drives ``tinymistral_248m`` at its published widths (random weights from
``--seed``) through the LUT-GEMV kernel and the continuous-batching
``Engine``, checks the results, and prints one line per phase.  The last
line of standard output is ``{"ok": true, "device": {...}}``.  Any failed
check exits non-zero before that line; so does a machine with no TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Kernel vs the HIGHEST-precision reference, as max|err| / max|ref|.  The
# kernel's MXU dot may round each f32 operand to bf16 (2**-9 relative);
# over K terms of random sign that leaves an error near 2e-3 of the
# output scale.  1e-2 is five times that, and a wrong code, LUT entry or
# scale errs by the whole output.
KERNEL_RTOL = 1e-2

# Emitted token vs the argmax of a teacher-forced HIGHEST-precision
# forward over the same quantized params, in logits.  A token's gap is at
# most the serving error of its own logit plus that of the argmax.  With
# a8 activations that error is set by activation codes flipping at their
# rounding boundary: in exact f32 arithmetic (host CPU, full width, 4 and
# 8 layers) int8 KV and the different attention order of prefill and
# decode already move logits by std 0.04, at most 0.22, as much as all of
# the a8 rounding does (std 0.035, max 0.21).  On the chip bf16 MXU
# operands flip more codes, which redraws that rounding noise (about
# sqrt(2) larger).  0.5 allows 0.25 for each of the two logits.  Logits
# spread with std 0.88 here, so a wrong token sits about 3.7 below the
# argmax.
LOGIT_TOL = 0.5

GROUP = 128


def _fail(msg: str) -> None:
    raise SystemExit(f"FAIL: {msg}")


@contextlib.contextmanager
def _dispatch(backend):
    """Route every quantized matmul to ``backend`` for the block (None
    keeps the default dispatch)."""
    from repro.models import sail_linear
    prev = sail_linear._BACKEND
    sail_linear.set_backend(backend or prev)
    try:
        yield
    finally:
        sail_linear.set_backend(prev)


def device_phase(min_count: int) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        _fail(f"no TPU found: JAX's first device is {d.platform!r} "
              f"({d.device_kind})")
    if len(devs) < min_count:
        _fail(f"{min_count} chips needed, {len(devs)} found")
    print(f"device: {d.device_kind}, {len(devs)} device(s), platform "
          f"{d.platform}", flush=True)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def quantized_shapes(cfg, bits: int = 4):
    """Distinct (K, N) of the matrices the engine quantizes, traced from
    ``quantize_params`` on the parameter shapes (nothing is computed)."""
    import jax
    from repro.models import lm
    from repro.models.sail_linear import (QTensor, QuantPolicy,
                                          StackedQTensor, quantize_params)
    from repro.serving.engine import EngineConfig
    policy = QuantPolicy(bits=bits, group_size=GROUP,
                         min_size=EngineConfig().min_size)
    shapes = jax.eval_shape(lambda: quantize_params(
        lm.init_params(jax.random.PRNGKey(0), cfg), policy)[0])
    leaves = jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda x: isinstance(x, (QTensor, StackedQTensor)))
    return sorted({(q.k, q.n) for q in leaves
                   if isinstance(q, (QTensor, StackedQTensor))})


def _random_qtensor(key, k: int, n: int, bits: int, abits):
    """A QTensor of uniform random codes and positive group scales."""
    import jax
    import jax.numpy as jnp
    from repro.core import quant
    kc, ks = jax.random.split(key)
    codes = jax.random.randint(kc, (k, n), 0, 1 << bits, dtype=jnp.int32)
    scales = 0.01 + 0.05 * jnp.abs(jax.random.normal(ks, (k // GROUP, n)))
    return quant.QTensor(
        packed=quant.pack_grouped(codes.astype(jnp.uint32), bits, GROUP),
        scales=scales, codebook=quant._uniform_codebook(bits), bits=bits,
        group_size=GROUP, k=k, abits=abits)


def kernel_phase(cfg, m: int = 8, seed: int = 0) -> None:
    """The compiled LUT-GEMV kernel at every quantized matrix shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.quant import quantize_activations
    from repro.kernels import default_interpret
    from repro.kernels.lut_gemv.ops import lut_matmul
    from repro.kernels.lut_gemv.ref import lut_matmul_ref, lut_matmul_ref_int

    if default_interpret():
        _fail("the platform chose the Pallas interpreter")
    bad = []
    key = jax.random.PRNGKey(seed)
    for plan, bits, abits in (("uniform:4", 4, None), ("uniform:4a8", 4, 8),
                              ("uniform:8", 8, None)):
        for k, n in quantized_shapes(cfg):
            key, kq, kx = jax.random.split(key, 3)
            qt = _random_qtensor(kq, k, n, bits, abits)
            x = jax.random.normal(kx, (m, k), jnp.float32)
            lowered = jax.jit(
                lambda x, qt: lut_matmul(x, qt, backend="pallas")).lower(x, qt)
            custom = "tpu_custom_call" in lowered.as_text()
            y = np.asarray(lut_matmul(x, qt, backend="pallas"))
            with jax.default_matmul_precision("highest"):
                if abits is None:
                    ref = lut_matmul_ref(x, qt)
                else:
                    ref = lut_matmul_ref_int(*quantize_activations(x, abits),
                                             qt)
            ref = np.asarray(ref)
            err = float(np.max(np.abs(y - ref)))
            rel = err / float(np.max(np.abs(ref)))
            ok = custom and np.isfinite(y).all() and rel <= KERNEL_RTOL
            print(f"kernel {plan} M={m} K={k} N={n}: max_abs={err:.3e} "
                  f"max_rel={rel:.3e} (tol {KERNEL_RTOL}) "
                  f"tpu_custom_call={custom} {'ok' if ok else 'FAIL'}",
                  flush=True)
            if not ok:
                bad.append((plan, k, n))
    if bad:
        _fail(f"kernel outside tolerance or not compiled: {bad}")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"kernel phase: ok; info: peak_bytes_in_use {peak}", flush=True)


def _prompts(cfg, n: int, lo: int, hi: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, size=int(rng.integers(lo, hi + 1)))
            .tolist() for _ in range(n)]


def logit_gaps(params, cfg, prompts, outs):
    """Per emitted token: max logit minus the emitted token's logit, from
    one teacher-forced ``lm.forward`` over prompt + output at HIGHEST
    precision through the jnp reference matmul."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import lm
    seqs = [p + o for p, o in zip(prompts, outs)]
    t = max(len(s) for s in seqs) - 1
    toks = np.zeros((len(seqs), t), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s) - 1] = s[:-1]
    with _dispatch("jnp"), jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, x: lm.forward(p, x, cfg)[0])(
            params, jnp.asarray(toks))
    gaps = []
    for i, (p, o) in enumerate(zip(prompts, outs)):
        rows = logits[i, len(p) - 1:len(p) - 1 + len(o)]
        picked = jnp.take_along_axis(rows, jnp.asarray(o)[:, None], 1)[:, 0]
        gaps.append(np.asarray(jnp.max(rows, axis=-1) - picked))
    return np.concatenate(gaps)


def kernel_in_steps(eng, cfg, prompt_len: int):
    """Whether the Pallas kernel sits in the engine's decode step (the
    trace the engine ran, from the jit cache) and in its prefill (traced
    now, as every eager prefill call traces it)."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    b = eng.ecfg.batch_size
    decode = lm.decode_step.trace(
        eng.params, jnp.zeros((b, 1), jnp.int32), eng.cache, cfg,
        quant_kv=True, active_mask=jnp.ones((b,), bool),
        block_tables=jnp.asarray(eng._tables_np)).jaxpr
    prefill = jax.make_jaxpr(functools.partial(
        lm.prefill, cfg=cfg, cache_len=prompt_len, quant_kv=True))(
        eng.params, jnp.zeros((1, prompt_len), jnp.int32))
    return "pallas_call" in str(decode), "pallas_call" in str(prefill)


def engine_phase(params, cfg, *, dispatch=None, tp: int = 1,
                 group_size: int = GROUP, cache_len: int = 2048,
                 n_requests: int = 8, prompt_lens=(64, 512),
                 max_new: int = 32, seed: int = 0) -> None:
    """Serve seeded requests through ``Engine`` submit/run and hold every
    emitted token to the teacher-forced reference."""
    import jax
    import numpy as np
    from repro.models import sail_linear
    from repro.serving.engine import Engine, EngineConfig

    with _dispatch(dispatch):
        name = (f"engine dispatch={sail_linear._BACKEND}"
                f"{'' if dispatch else ' (default)'} tp={tp}")
        t0 = time.perf_counter()
        eng = Engine(params, cfg, EngineConfig(
            plan="uniform:4a8", kv_block_size=16, batch_size=8,
            cache_len=cache_len, quant_kv=True, group_size=group_size,
            tp=tp, wire=32))
        # warm-up: compiles the decode step and one prefill shape
        eng.submit(_prompts(cfg, 1, *prompt_lens, seed + 1)[0],
                   max_new_tokens=4)
        eng.run()
        t_warm = time.perf_counter() - t0
        dec_s0, dec_t0 = eng.decode_seconds, eng._decode_tokens
        prompts = _prompts(cfg, n_requests, *prompt_lens, seed)
        uids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        t1 = time.perf_counter()
        eng.run()
        t_run = time.perf_counter() - t1
        kernel = ""
        if tp == 1:      # the TP steps are shard_map wrappers, not lm's jits
            pallas = sail_linear._BACKEND == "pallas"
            found = kernel_in_steps(eng, cfg, prompt_lens[1])
            if found != (pallas, pallas):
                _fail(f"{name}: Pallas kernel in (decode, prefill) = {found}")
            kernel = f"; Pallas kernel in decode/prefill {found[0]}/{found[1]}"
    if tp > 1:
        used = [(d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.devices()]
        print(f"{name}: bytes_in_use per device {used}", flush=True)
    outs = [list(eng.completions[u].tokens) for u in uids]
    short = [len(o) for o in outs if len(o) != max_new]
    if short:
        _fail(f"{name}: requests ended early with {short} tokens")
    ref_params = eng.params
    if tp > 1:       # the reference runs unsharded, on one device
        ref_params = jax.device_put(eng.params, jax.devices()[0])
    gaps = logit_gaps(ref_params, cfg, prompts, outs)
    tps = ((eng._decode_tokens - dec_t0)
           / max(eng.decode_seconds - dec_s0, 1e-9))
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"{name}: {len(outs)} requests x {max_new} tokens, "
          f"max logit gap {float(gaps.max()):.4f} (tol {LOGIT_TOL}), "
          f"argmax share {float(np.mean(gaps == 0)):.3f}; info: warm-up "
          f"incl. compile {t_warm:.1f}s, run {t_run:.1f}s, decode "
          f"{tps:.1f} tok/s, peak_bytes_in_use {peak}{kernel}", flush=True)
    if not np.isfinite(gaps).all() or gaps.max() > LOGIT_TOL:
        _fail(f"{name}: emitted tokens off the reference argmax by up to "
              f"{float(gaps.max()):.4f} logits")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tp", type=int, default=1, choices=(1, 4),
                    help="4: only the engine phase, at tp=4 and at tp=1 "
                         "on the same devices")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import repro.configs.tinymistral_248m as tinymistral
    from repro.models import lm

    device = device_phase(args.tp)
    cfg = tinymistral.full()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    if args.tp == 1:
        kernel_phase(cfg, seed=args.seed)
        for dispatch in (None, "pallas"):
            engine_phase(params, cfg, dispatch=dispatch, seed=args.seed)
    else:
        for tp in (args.tp, 1):
            engine_phase(params, cfg, tp=tp, seed=args.seed)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
