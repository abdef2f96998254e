"""The realized int-activation serve path.

Proves the executed datapath matches the ``abits`` semantics the
allocator prices: quantized activation codes enter the Pallas LUT-GEMV
kernel directly (dequant fused into the LUT build, per-token scale at
the accumulator store), bit-exact against the jnp oracle across the
(wbits x abits) grid — no fake-quant anywhere in the serve path — and
decode through the engine is token-identical across backends.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.kernels.lut_gemv import ops as lut_ops
from repro.kernels.lut_gemv import ref as lut_ref
from repro.models import lm, sail_linear
from repro.models.common import ModelConfig
from repro.models.sail_linear import QuantPolicy, einsum_q, mm

# Single-block shape (bm=8, bk=256, bn=256): no padding, one K step, so
# kernel and oracle run the identical f32 op sequence -> bitwise equal.
ALIGNED = (8, 256, 256)
GS = 64


def _qt(wbits, abits, k, n, gs=GS, seed=0):
    w = jax.random.normal(jax.random.PRNGKey(seed), (k, n))
    return dataclasses.replace(quant.quantize(w, wbits, gs), abits=abits)


# ---------------------------------------------------------------------------
# kernel grid: pallas int path == jnp oracle, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wbits", [1, 2, 3, 4, 8])
@pytest.mark.parametrize("abits", [4, 6, 8, None])
def test_kernel_grid_bit_exact(wbits, abits):
    m, k, n = ALIGNED
    qt = _qt(wbits, abits, k, n, seed=wbits)
    x = jax.random.normal(jax.random.PRNGKey(17), (m, k))
    y = lut_ops.lut_matmul(x, qt, backend="pallas", interpret=True)
    assert y.shape == (m, n)
    if abits is None:
        y_ref = lut_ref.lut_matmul_ref(x, qt)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-6, atol=1e-6)
    else:
        xq, xs = quant.quantize_activations(x, abits)
        y_ref = lut_ref.lut_matmul_ref_int(xq, xs, qt)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


@pytest.mark.parametrize("abits", [4, 8])
def test_kernel_unaligned_shapes(abits):
    # m/n off the block grid: padding uses zero activation codes (exactly
    # zero contribution) so the valid slice still matches the oracle
    m, k, n = 3, 96, 100
    qt = _qt(4, abits, k, n, gs=32)
    x = jax.random.normal(jax.random.PRNGKey(5), (m, k))
    y = lut_ops.lut_matmul(x, qt, backend="pallas", interpret=True)
    xq, xs = quant.quantize_activations(x, abits)
    y_ref = lut_ref.lut_matmul_ref_int(xq, xs, qt)
    assert y.shape == (m, n)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_jnp_backend_is_the_int_oracle():
    m, k, n = ALIGNED
    qt = _qt(4, 8, k, n)
    x = jax.random.normal(jax.random.PRNGKey(2), (m, k))
    y = lut_ops.lut_matmul(x, qt, backend="jnp")
    xq, xs = quant.quantize_activations(x, 8)
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(lut_ref.lut_matmul_ref_int(xq, xs, qt)))


def test_int_path_is_not_fake_quant():
    """Serve semantics are (x_q @ W) * s — scale after the int matmul —
    not (x_q * s) @ W fake-quant.  The two differ by f32 rounding."""
    m, k, n = ALIGNED
    qt = _qt(4, 4, k, n)
    x = jax.random.normal(jax.random.PRNGKey(3), (m, k))
    got = np.asarray(lut_ops.lut_matmul(x, qt, backend="jnp"))
    xq, xs = quant.quantize_activations(x, 4)
    oracle = np.asarray(lut_ref.lut_matmul_ref_int(xq, xs, qt))
    fake = np.asarray(lut_ref.lut_matmul_ref(
        (xq.astype(jnp.float32) * xs), qt))
    np.testing.assert_array_equal(got, oracle)
    if not np.array_equal(fake, oracle):      # rounding almost surely differs
        assert not np.array_equal(got, fake)


# ---------------------------------------------------------------------------
# model entry points: mm / einsum_q dispatch to the int path on abits
# ---------------------------------------------------------------------------

def test_mm_serves_int_path():
    m, k, n = ALIGNED
    qt = _qt(3, 6, k, n)
    x = jax.random.normal(jax.random.PRNGKey(7), (m, k))
    got = mm(x, qt)
    xq, xs = quant.quantize_activations(x, 6)
    want = lut_ref.lut_matmul_ref_int(xq, xs, qt, out_dtype=x.dtype)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mm_leading_dims_int_path():
    qt = _qt(4, 8, 64, 32, gs=32)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 3, 64))
    got = mm(x, qt)
    assert got.shape == (2, 3, 32)
    xq, xs = quant.quantize_activations(x.reshape(-1, 64), 8)
    want = lut_ref.lut_matmul_ref_int(xq, xs, qt).reshape(2, 3, 32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_einsum_q_int_path_moe_spec():
    # the dispatch einsum: x[t,d] x experts[e,d,f] -> y[t,e,f]
    e, d, f = 2, 64, 32
    w = jax.random.normal(jax.random.PRNGKey(11), (e, d, f))
    pol = QuantPolicy(bits=4, group_size=32, min_size=1)
    st = sail_linear._quantize_stacked(w, 4, pol, abits=8)
    x = jax.random.normal(jax.random.PRNGKey(12), (3, d))
    got = einsum_q("td,edf->tef", x, st)
    wd = sail_linear.dequantize_any(st)
    xq, xs = quant.quantize_activations(x, 8)
    y = jnp.einsum("td,edf->tef", xq.astype(jnp.float32), wd)
    want = (y * xs[..., 0][:, None, None]).astype(x.dtype)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_einsum_scale_to_out_mapping():
    xs = jnp.arange(6, dtype=jnp.float32).reshape(3, 2, 1) + 1.0
    out = sail_linear._einsum_scale_to_out("ted,edf->tef", (3, 2, 64), xs)
    assert out is not None and out.shape == (3, 2, 1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(xs))
    # contracted subscript in the output -> not mappable, caller folds
    assert sail_linear._einsum_scale_to_out(
        "td,de->tde", (3, 64), xs[:, 0]) is None


def test_apply_act_quant_only_unwraps_probes():
    """Fake-quant survives only inside the ActQuantWeight probe; a plain
    QTensor passes through mm with activations untouched until the kernel."""
    qt = _qt(4, 8, 64, 32, gs=32)
    x = jax.random.normal(jax.random.PRNGKey(13), (4, 64))
    x2, w2 = sail_linear._apply_act_quant(x, qt)
    assert x2 is x and w2 is qt
    probe = sail_linear.ActQuantWeight(
        w=jnp.eye(64), gate=jnp.asarray(1.0), abits=8)
    x3, w3 = sail_linear._apply_act_quant(x, probe)
    np.testing.assert_array_equal(
        np.asarray(x3), np.asarray(sail_linear.act_fake_quant(x, 8)))
    assert isinstance(w3, jax.Array)


# ---------------------------------------------------------------------------
# engine decode under an a<b> plan: token-identical across backends
# ---------------------------------------------------------------------------

def _tiny_cfg():
    return ModelConfig(name="tiny", family="dense", vocab=64, d_model=32,
                       n_layers=2, n_heads=4, n_kv=2, d_ff=64, act="swiglu",
                       attn_chunk=16, max_seq=128)


def test_engine_decode_token_identity_across_backends():
    from repro.serving.engine import Engine, EngineConfig
    cfg = _tiny_cfg()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)

    def decode(backend):
        sail_linear.set_backend(backend)
        try:
            eng = Engine(params, cfg, EngineConfig(
                batch_size=2, cache_len=32, quantize=True, ql=8,
                group_size=32, quant_kv=False,
                plan="rules:mlp=4a6,default=6a8"))
            abits = {q.abits for _, q in _iter_qtensors(eng.params)}
            assert abits & {4, 6, 8}      # the int path is actually in play
            eng.submit([1, 2, 3], max_new_tokens=6)
            done = eng.run()
            assert len(done) == 1
            return list(done[0].tokens)
        finally:
            sail_linear.set_backend("jnp")

    assert decode("jnp") == decode("pallas")


def test_set_backend_retraces_jitted_programs():
    """A program traced under one backend must not keep serving it after
    set_backend: the switch is read at trace time, outside any cache key."""
    qt = _qt(4, None, 256, 256)
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 256))
    traced = []

    @jax.jit
    def f(x, w):
        traced.append(sail_linear._BACKEND)
        return mm(x, w)

    try:
        f(x, qt)
        sail_linear.set_backend("pallas")
        f(x, qt)
        assert "pallas_call" in str(f.trace(x, qt).jaxpr)
    finally:
        sail_linear.set_backend("jnp")
    f(x, qt)
    assert traced == ["jnp", "pallas", "jnp"]


def _iter_qtensors(tree, prefix=""):
    from repro.core.quant import QTensor
    from repro.models.sail_linear import StackedQTensor
    flat = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, (QTensor, StackedQTensor)))[0]
    for path, leaf in flat:
        if isinstance(leaf, (QTensor, StackedQTensor)):
            yield jax.tree_util.keystr(path), leaf
