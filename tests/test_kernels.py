"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, strategies as st

from repro.core import quant
from repro.kernels.decode_attn import kernel as da_kernel, ref as da_ref
from repro.kernels.lut_gemv import ops as lut_ops, ref as lut_ref
from repro.kernels.typeconv import ops as tc_ops
from repro.models import blocks


@pytest.mark.parametrize("bits", [2, 3, 4, 5, 6, 8])
@pytest.mark.parametrize("mkn", [(8, 256, 128), (3, 130, 70), (16, 512, 384),
                                 (1, 64, 512)])
def test_lut_matmul_sweep(bits, mkn):
    m, k, n = mkn
    gs = 64
    kk = -(-k // gs) * gs
    w = jax.random.normal(jax.random.PRNGKey(bits), (kk, n))
    qt = quant.quantize(w, bits, gs)
    x = jax.random.normal(jax.random.PRNGKey(m), (m, kk))
    y = lut_ops.lut_matmul(x, qt, backend="pallas", interpret=True)
    y_ref = lut_ref.lut_matmul_ref(x, qt)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lut_matmul_dtypes(dtype):
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 128))
    qt = quant.quantize(w, 4, 128)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 256)).astype(dtype)
    y = lut_ops.lut_matmul(x, qt, out_dtype=dtype, backend="pallas")
    y_ref = lut_ref.lut_matmul_ref(x, qt, out_dtype=dtype)
    assert y.dtype == dtype
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_lut_matmul_nf_codebook():
    from repro.core.quant import nf_codebook
    w = jax.random.normal(jax.random.PRNGKey(2), (256, 64))
    qt = quant.quantize(w, 4, 64, codebook=nf_codebook(4))
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 256))
    y = lut_ops.lut_matmul(x, qt, backend="pallas")
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(lut_ref.lut_matmul_ref(x, qt)),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("n", [8, 16, 25])
def test_typeconv_kernel(n):
    lim = 1 << (n - 1)
    vals = np.random.default_rng(n).integers(
        -lim + 1, lim, size=777).astype(np.int32)
    out = tc_ops.int_to_f32(jnp.asarray(vals), n=n, backend="pallas")
    assert (np.asarray(out) == vals.astype(np.float32)).all()


# (H, KV, Dh) of tinymistral_248m and of mellum2_12b_a2_5b
ATTN_SHAPES = {"tinymistral": (32, 8, 32), "mellum2": (32, 4, 128)}
BS, NBP = 16, 12                     # block size, table columns


def _paged_pool(h, kv, dh, layers=3, seed=0):
    """A random int8 KV pool in ``lm.init_paged_cache``'s layout (codes
    ``[L, NB, BS, KV*Dh]``, scales ``[L, NB, KV*BS]`` heads-major, the
    last block the trash block), six lanes' tables over a shuffle of the
    other blocks, a query per lane, and the config that reads them.  Lane
    positions: 0, the last slot of a block, the first slot of a block,
    the last slot of the table, an inactive lane (its table all trash),
    and one in the second block."""
    from repro.models.common import ModelConfig
    cfg = ModelConfig(name="paged", family="dense", vocab=16,
                      d_model=h * dh, n_layers=layers, n_heads=h, n_kv=kv,
                      d_ff=16)
    rng = np.random.default_rng(seed)
    lanes, nb = 6, 6 * NBP + 1
    kq, ks = quant.quantize_kv(jnp.asarray(
        rng.normal(size=(layers, nb, BS, kv, dh)), jnp.float32))
    vq, vs = quant.quantize_kv(jnp.asarray(
        rng.normal(size=(layers, nb, BS, kv, dh)), jnp.float32))
    codes = lambda c: c.reshape(layers, nb, BS, kv * dh)
    scales = lambda s: jnp.swapaxes(s[..., 0], 2, 3).reshape(layers, nb,
                                                             kv * BS)
    pool = (codes(kq), scales(ks), codes(vq), scales(vs))
    tables = rng.permutation(nb - 1)[:lanes * NBP].reshape(lanes, NBP)
    tables[4] = nb - 1
    pos = np.asarray([0, BS - 1, BS, BS * NBP - 1, 37, 21], np.int32)
    active = np.asarray([True, True, True, True, False, True])
    q = jnp.asarray(rng.normal(size=(lanes, h, dh)), jnp.float32)
    return (cfg, pool, jnp.asarray(tables, jnp.int32), jnp.asarray(pos),
            active, q)


def _kernel(q, pool, layer, pos, active, tables, window):
    lanes = jnp.where(jnp.asarray(active), pos, -1)
    return da_kernel.paged_decode_attention(
        q, *pool, layer, lanes, tables, window, interpret=True)


def _atol(pool):
    """The kernel rounds the query to bf16, as the TPU's default matmul
    precision rounds the jnp path's operands; the oracles here are given
    that same rounded query.  What is left is the kernel's bf16 rounding
    of each probability times its V scale (the V codes are exact in
    bf16): at most 2^-9 of each term of a convex combination, so at most
    2^-9 * max|V| in all; the bound allows twice that, plus f32 rounding
    of the two softmax orders (1e-5)."""
    v = pool[2].astype(jnp.float32).reshape(pool[2].shape[:2] + (BS, -1))
    s = pool[3].reshape(pool[3].shape[:2] + (-1, BS))          # [L,NB,KV,BS]
    vmax = float(jnp.max(jnp.abs(v)) * jnp.max(s))
    return 2.0 ** -8 * vmax + 1e-5


def _bf16(q):
    return q.astype(jnp.bfloat16).astype(jnp.float32)


@pytest.mark.parametrize("window", [None, 40])
@pytest.mark.parametrize("shape", ATTN_SHAPES, ids=str)
def test_decode_attn_sweep(shape, window):
    """The paged kernel against ``ref.decode_attention_ref`` over each
    lane's table gathered into a contiguous cache (``lengths`` = position
    + 1); tolerance as ``_atol`` derives it.  The inactive lane reads
    nothing and returns zeros."""
    cfg, pool, tables, pos, active, q = _paged_pool(*ATTN_SHAPES[shape])
    layer, s = 1, BS * NBP
    out = np.asarray(_kernel(q, pool, layer, pos, active, tables,
                             s if window is None else window))
    view = lambda a: blocks.paged_view(a[layer, tables], cfg)
    ref = np.asarray(da_ref.decode_attention_ref(
        _bf16(q), view(pool[0]), view(pool[2]), pos + 1, view(pool[1]),
        view(pool[3]), window))
    np.testing.assert_allclose(out[active], ref[active], rtol=0,
                               atol=_atol(pool))
    assert (out[~active] == 0).all()


@pytest.mark.parametrize("window", [None, 40])
@pytest.mark.parametrize("shape", ATTN_SHAPES, ids=str)
def test_paged_decode_attn_matches_jnp_path(shape, window):
    """The kernel against the decode step's own jnp path (gather,
    dequantize, ``_decode_attend``), which ``_paged_int8_attend`` runs on
    the CPU, over the same pool and tables."""
    cfg, pool, tables, pos, active, q = _paged_pool(*ATTN_SHAPES[shape])
    if window is not None:
        cfg = dataclasses.replace(cfg, window=window)
    layer, s = 2, BS * NBP
    ref = np.asarray(blocks._paged_int8_attend(
        _bf16(q)[:, None], pool, layer, pos, tables, jnp.asarray(active),
        cfg, s)[:, 0])
    out = np.asarray(_kernel(q, pool, layer, pos, active, tables,
                             cfg.window or s))
    np.testing.assert_allclose(out[active], ref[active], rtol=0,
                               atol=_atol(pool))
    assert (out[~active] == 0).all()


@pytest.mark.parametrize("shape", ATTN_SHAPES, ids=str)
def test_paged_decode_attn_traced_window_in_layer_scan(shape):
    """Inside a ``lax.scan`` over layers, each layer's window a traced
    value (a full layer, then two windows smaller than the longest
    lane's context), as Mellum2's sliding and full layers share one
    scan: the kernel matches ``_decode_attend`` layer by layer."""
    cfg, pool, tables, pos, active, q = _paged_pool(*ATTN_SHAPES[shape])
    s = BS * NBP
    windows = jnp.asarray([1 << 30, 40, 100], jnp.int32)

    def body(_, inp):
        layer, w = inp
        view = lambda a: blocks.paged_view(a[layer, tables], cfg)
        kf = view(pool[0]).astype(jnp.float32) * view(pool[1])
        vf = view(pool[2]).astype(jnp.float32) * view(pool[3])
        ref = blocks._decode_attend(_bf16(q)[:, None], kf, vf, pos, cfg, s,
                                    w)[:, 0]
        return None, (_kernel(q, pool, layer, pos, active, tables, w), ref)

    _, (out, ref) = jax.lax.scan(body, None, (jnp.arange(3), windows))
    out, ref = np.asarray(out), np.asarray(ref)
    np.testing.assert_allclose(out[:, active], ref[:, active], rtol=0,
                               atol=_atol(pool))
    assert not np.allclose(ref[0, active], ref[1, active])  # windows bind
    assert (out[:, ~active] == 0).all()


@pytest.mark.parametrize("arch", ["tinymistral_248m", "mellum2_12b_a2_5b"])
def test_engine_decode_runs_the_kernel_as_the_jnp_path(arch, monkeypatch):
    """The engine's paged int8 decode steps with the kernel's branch taken
    (as lowering for a TPU takes it; interpreted here): in every layer
    call, each active lane's output matches the jnp branch given the same
    bf16-rounded query, within ``_atol``'s bound (2^-8 of the largest V);
    inactive lanes read nothing.  Covers the layer index and each layer's
    window (Mellum2: three sliding layers, one full) as the layer scan
    passes them, and the active mask."""
    import functools
    import repro.configs as C
    from repro.models import lm
    from repro.serving.engine import Engine, EngineConfig
    calls = []

    def kernel_branch(*args, tpu, default):
        out = tpu(*args)
        ref = default(_bf16(args[0]), *args[1:])
        bound = 2.0 ** -8 * 127 * jnp.max(args[4]) + 1e-5
        jax.debug.callback(
            lambda *v: calls.append([np.asarray(x) for x in v]),
            jnp.max(jnp.abs(out - ref), axis=(1, 2, 3)), bound,
            jnp.all(out == 0, axis=(1, 2, 3)))
        return out

    cfg = C.get_smoke(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    monkeypatch.setattr(jax.lax, "platform_dependent", kernel_branch)
    monkeypatch.setattr(blocks, "paged_decode_attention", functools.partial(
        da_kernel.paged_decode_attention, interpret=True))
    jax.clear_caches()
    try:
        eng = Engine(params, cfg, EngineConfig(
            batch_size=3, cache_len=48, kv_block_size=4, quantize=True,
            group_size=32, plan="uniform:4a8", eos_token=-1))
        for n, new in ((13, 9), (21, 14), (6, 5), (30, 12)):
            eng.submit([(7 * i + n) % cfg.vocab for i in range(n)], new)
        eng.run()
    finally:
        jax.clear_caches()
    assert len(calls) == eng.decode_iterations * cfg.n_layers
    for diff, bound, empty in calls:
        assert (diff[~empty] <= bound).all()
    # lanes idle in some step (4 requests on 3 lanes) read nothing
    assert any(empty.any() for _, _, empty in calls)


@pytest.mark.parametrize("shape", ATTN_SHAPES, ids=str)
def test_paged_write_whole_rows_equals_element_scatter(shape):
    """Decode's scale write (``one_per_block``: read each block row, set
    the token's KV columns, write the row back) leaves the pool as the
    element scatter does when each live block takes one token; retired
    lanes all write the trash block, whose contents are dead either
    way."""
    h, kv, dh = ATTN_SHAPES[shape]
    _, pool, tables, pos, active, _ = _paged_pool(h, kv, dh)
    layer, trash = 1, pool[1].shape[1] - 1
    phys = jnp.where(jnp.asarray(active), tables[:, 0], trash)
    off = pos % BS
    val = jnp.asarray(np.random.default_rng(3).random((6, kv, 1)),
                      jnp.float32)
    rows = blocks.paged_write(pool[1], (layer,), phys, off, val,
                              one_per_block=True)
    elems = blocks.paged_write(pool[1], (layer,), phys, off, val)
    live = np.ones(pool[1].shape[1], bool)
    live[trash] = False
    np.testing.assert_array_equal(np.asarray(rows)[:, live],
                                  np.asarray(elems)[:, live])
    assert not np.array_equal(np.asarray(rows), np.asarray(pool[1]))


@settings(max_examples=10, deadline=None)
@given(bits=st.sampled_from([2, 4, 8]), m=st.integers(1, 9),
       kmul=st.integers(1, 3), n=st.integers(8, 130))
def test_property_lut_matmul(bits, m, kmul, n):
    k = 64 * kmul
    w = jax.random.normal(jax.random.PRNGKey(bits + m), (k, n))
    qt = quant.quantize(w, bits, 64)
    x = jax.random.normal(jax.random.PRNGKey(n), (m, k))
    y = lut_ops.lut_matmul(x, qt, backend="pallas")
    y_ref = lut_ref.lut_matmul_ref(x, qt)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)
