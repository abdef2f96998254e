"""Per-architecture smoke tests + decode/forward consistency (the
assignment's reduced-config requirement) + SAIL quantized-serving path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import encdec, lm
from repro.models.sail_linear import QuantPolicy, quantize_params

ARCHS = C.ARCHS


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_decode(arch):
    cfg = C.get_smoke(arch)
    key = jax.random.PRNGKey(0)
    if cfg.family == "encdec":
        params = encdec.init_params(key, cfg)
        frames = jax.random.normal(key, (2, cfg.enc_seq, cfg.d_model))
        toks = jax.random.randint(key, (2, 9), 0, cfg.vocab)
        loss, _ = encdec.loss_fn(params, {"frames": frames, "tokens": toks},
                                 cfg)
        assert np.isfinite(float(loss))
        cache = encdec.serve_prefill(params, frames, cfg, cache_len=16)
        logits, cache = encdec.serve_decode_step(params, toks[:, :1], cache,
                                                 cfg)
        assert logits.shape == (2, cfg.vocab)
        assert np.isfinite(np.asarray(logits)).all()
        return
    params = lm.init_params(key, cfg)
    pre = (jax.random.normal(key, (2, cfg.vision_tokens, cfg.d_model))
           if cfg.frontend == "vision" else None)
    toks = jax.random.randint(key, (2, 17), 0, cfg.vocab)
    loss, _ = lm.loss_fn(params, {"tokens": toks, "prefix_embeds": pre}, cfg)
    assert np.isfinite(float(loss))
    logits, cache = lm.prefill(params, toks[:, :-1], cfg, cache_len=32,
                               prefix_embeds=pre)
    logits, cache = lm.decode_step(params, toks[:, :1], cache, cfg)
    assert logits.shape == (2, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("arch", ["llama3_2_1b", "qwen3_0_6b", "hymba_1_5b",
                                  "mixtral_8x7b", "xlstm_350m",
                                  "granite_moe_1b_a400m"])
def test_decode_matches_forward(arch):
    """Prefill+decode must reproduce teacher-forced logits (KV-cache
    correctness — ring buffer, RoPE offsets, SSM/xLSTM state carry)."""
    cfg = C.get_smoke(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)
    logits_full, _ = lm.forward(params, toks, cfg, moe_mode="dense")
    logits_p, cache = lm.prefill(params, toks[:, :8], cfg, cache_len=32)
    np.testing.assert_allclose(np.asarray(logits_p),
                               np.asarray(logits_full[:, 7]),
                               rtol=2e-4, atol=2e-4)
    for t in range(8, 12):
        logits_d, cache = lm.decode_step(params, toks[:, t:t + 1], cache,
                                         cfg)
        np.testing.assert_allclose(np.asarray(logits_d),
                                   np.asarray(logits_full[:, t]),
                                   rtol=2e-4, atol=2e-4)


def test_windowed_ring_cache_matches_full():
    """SWA arch: decoding with a window-sized ring cache must equal
    decoding with a full-length cache (window masking correctness)."""
    cfg = dataclasses.replace(C.get_smoke("h2o_danube_3_4b"), window=16)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 30), 0, cfg.vocab)
    _, cache_full = lm.prefill(params, toks[:, :8], cfg, cache_len=64)
    _, cache_ring = lm.prefill(params, toks[:, :8], cfg, cache_len=16)
    for t in range(8, 30):
        lf, cache_full = lm.decode_step(params, toks[:, t:t + 1],
                                        cache_full, cfg)
        lr, cache_ring = lm.decode_step(params, toks[:, t:t + 1],
                                        cache_ring, cfg)
        np.testing.assert_allclose(np.asarray(lr), np.asarray(lf),
                                   rtol=2e-4, atol=2e-4)


def test_quant_kv_decode_close():
    cfg = C.get_smoke("llama3_2_1b")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, cfg.vocab)
    lf, cf = lm.prefill(params, toks[:, :8], cfg, cache_len=32)
    lq, cq = lm.prefill(params, toks[:, :8], cfg, cache_len=32,
                        quant_kv=True)
    # int8 KV: small relative error on logits
    denom = float(jnp.abs(lf).max())
    assert float(jnp.abs(lq - lf).max()) / denom < 0.08
    lf2, _ = lm.decode_step(params, toks[:, 8:9], cf, cfg)
    lq2, _ = lm.decode_step(params, toks[:, 8:9], cq, cfg, quant_kv=True)
    assert float(jnp.abs(lq2 - lf2).max()) / denom < 0.1


@pytest.mark.parametrize("ql", [2, 4, 8])
def test_sail_quantized_serving(ql):
    """Full SAIL path: quantized weights + quantized KV still decode to
    finite, vocab-shaped logits; Q8 stays close to f32."""
    cfg = C.get_smoke("tinymistral_248m")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    qp, b0, b1 = quantize_params(params, QuantPolicy(bits=ql, group_size=32,
                                                     min_size=1024))
    assert b1 < b0
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    lf, cf = lm.prefill(params, toks, cfg, cache_len=16)
    lq, cq = lm.prefill(qp, toks, cfg, cache_len=16)
    assert np.isfinite(np.asarray(lq)).all()
    if ql == 8:
        corr = np.corrcoef(np.asarray(lf).ravel(), np.asarray(lq).ravel())
        assert corr[0, 1] > 0.98
    lg, _ = lm.decode_step(qp, toks[:, :1], cq, cfg)
    assert lg.shape == (2, cfg.vocab) and np.isfinite(np.asarray(lg)).all()


def test_param_count_formula():
    for arch in ["llama2_7b", "llama2_13b", "mixtral_8x7b"]:
        cfg = C.get_config(arch)
        target = {"llama2_7b": 6.74e9, "llama2_13b": 13.0e9,
                  "mixtral_8x7b": 46.7e9}[arch]
        assert abs(cfg.param_count() - target) / target < 0.08, \
            (arch, cfg.param_count())
    mx = C.get_config("mixtral_8x7b")
    assert abs(mx.active_param_count() - 12.9e9) / 12.9e9 < 0.15


def test_jitted_paged_prefill_matches_eager_prefill_and_scatter():
    """``lm.prefill_into_blocks``, one program, against eager ``prefill``
    then ``_scatter_blocks`` on the same 4-bit a8 weights and int8 pool:
    a padded bucket (prompts 10 and 7 in 12 positions) and a shared
    prefix block on lane 0, whose rows and the padding rows go to the
    trash block.  Logits agree to 1e-5; in every block but the trash one
    the int8 codes are equal and the f32 scales within one ulp (XLA's CPU
    backend rounds a fused ``absmax / 127`` differently from the lone
    op); the shared block keeps what it held."""
    cfg = C.get_smoke("tinymistral_248m")
    params, _, _ = quantize_params(
        lm.init_params(jax.random.PRNGKey(0), cfg),
        QuantPolicy(bits=4, group_size=32, min_size=1024, act_bits=8))
    bs, padded, n_blocks = 4, 12, 8
    trash = n_blocks
    pool = lm.init_paged_cache(params, cfg, 2, n_blocks + 1, bs, True)
    rng = np.random.default_rng(0)
    pool["layers"] = {   # old contents, so that an unwritten block shows
        name: jnp.asarray(rng.integers(-100, 100, a.shape).astype(a.dtype))
        for name, a in pool["layers"].items()}
    lengths = np.asarray([10, 7], np.int32)
    toks = np.zeros((2, padded), np.int32)
    for i, n in enumerate(lengths):
        toks[i, :n] = rng.integers(0, cfg.vocab, n)
    tables, shared = ([1, 2, 3], [4, 5]), (1, 0)
    phys = np.full((2, padded), trash, np.int32)
    for i, n in enumerate(lengths):
        for t in range(n):
            if t // bs >= shared[i]:
                phys[i, t] = tables[i][t // bs]
    offs = np.tile(np.arange(padded, dtype=np.int32) % bs, (2, 1))
    slots = np.asarray([0, 1], np.int32)
    copy = lambda c: jax.tree_util.tree_map(lambda a: jnp.array(a), c)

    logits_e, fresh = lm.prefill(params, jnp.asarray(toks), cfg,
                                 cache_len=padded, quant_kv=True,
                                 lengths=jnp.asarray(lengths))
    pool_e = lm._scatter_blocks(copy(pool), fresh, jnp.asarray(slots),
                                jnp.asarray(phys.ravel()),
                                jnp.asarray(offs.ravel()))
    logits_j, pool_j = lm.prefill_into_blocks(
        params, jnp.asarray(toks), copy(pool), slots, phys.ravel(),
        offs.ravel(), cfg, quant_kv=True, lengths=jnp.asarray(lengths))

    np.testing.assert_allclose(np.asarray(logits_j), np.asarray(logits_e),
                               rtol=0, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(pool_j["length"]),
                                  np.asarray(pool_e["length"]))
    for name, a in pool_j["layers"].items():
        got = np.asarray(a)[:, :trash]
        want = np.asarray(pool_e["layers"][name])[:, :trash]
        if a.dtype == jnp.int8:
            np.testing.assert_array_equal(got, want, err_msg=name)
        else:
            np.testing.assert_array_max_ulp(got, want, maxulp=1)
        np.testing.assert_array_equal(
            np.asarray(a)[:, 1], np.asarray(pool["layers"][name])[:, 1],
            err_msg=f"shared block of {name} rewritten")
        assert not np.array_equal(np.asarray(a)[:, 2],
                                  np.asarray(pool["layers"][name])[:, 2])
