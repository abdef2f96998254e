"""Mellum2's mechanisms at smoke size: layers of two attention kinds
(sliding windowed, full under YaRN), a routed dropless MoE, and the
closed-form quantizer, each held to a plain reference.

The engine runs the model through its normal path (``submit``/``step``,
paged pool) and its logits are compared with ``bench/reference/
moe_swa_gqa.py``'s full teacher-forced forward over the same tokens."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import repro.configs as C  # noqa: E402
from bench.reference import moe_swa_gqa as ref  # noqa: E402
from repro.core import quant  # noqa: E402
from repro.models import lm, moe  # noqa: E402
from repro.models.sail_linear import QuantPolicy, quantize_params  # noqa: E402
from repro.serving.engine import Engine, EngineConfig  # noqa: E402

CFG = C.get_smoke("mellum2_12b_a2_5b")
M = dataclasses.asdict(CFG)
SEED = 2**33 + 5
# prompts past the window (8) and past YaRN's original 16 positions;
# decoding runs on to position 40
PROMPTS = ((3, 13), (5, 21))              # (token offset, length)
MAX_NEW = 20
F32 = {"weight_bits": None, "act_bits": None, "group_size": 32,
       "kv_bits": 32, "dtype": "float32"}
Q4A8 = {"weight_bits": 4, "act_bits": 8, "group_size": 32, "kv_bits": 8,
        "dtype": "float32"}


def _serve(params, plan):
    """Serve PROMPTS through the engine; each request's logits per
    served token, keyed by prompt."""
    eng = Engine(params, CFG, EngineConfig(
        batch_size=2, cache_len=64, kv_block_size=4, prompt_bucket=8,
        group_size=32, quantize=plan is not None, plan=plan,
        quant_kv=plan is not None, eos_token=-1))
    rows = {}
    sample = eng._sample

    def recording(logits, uids=None, indices=None, **kw):
        host = np.asarray(logits)
        for i, (u, k) in enumerate(zip(np.asarray(uids), np.asarray(indices))):
            if u:
                rows.setdefault(int(u), {})[int(k)] = host[i]
        return sample(logits, uids, indices, **kw)

    eng._sample = recording
    uids = {}
    for off, n in PROMPTS:
        prompt = [(off + 7 * i) % CFG.vocab for i in range(n)]
        uids[eng.submit(prompt, MAX_NEW)] = prompt
    done = {c.uid: c for c in eng.run()}
    out = []
    for uid, prompt in uids.items():
        got = np.stack([rows[uid][i] for i in range(MAX_NEW)])
        out.append((prompt, done[uid].tokens, got))
    return out, eng


@pytest.fixture(scope="module")
def params():
    return ref.make_params(M, SEED)


@pytest.fixture(scope="module")
def served(params):
    return {"f32": _serve(params, None)[0],
            "q4a8": _serve(params, "uniform:4a8")[0]}


def _gap(params, runs, m, prec):
    """Largest |program - reference| logit over every served position,
    and the reference's largest |logit|."""
    worst, scale = 0.0, 0.0
    for prompt, tokens, got in runs:
        seq = prompt + tokens[:-1]
        want = np.asarray(ref.logits(params, seq, len(prompt), m,
                                     prec))[len(prompt) - 1:]
        worst = max(worst, float(np.abs(got - want).max()))
        scale = max(scale, float(np.abs(want).max()))
    return worst, scale


# f32: the program and the reference differ only in the order of float32
# sums (chunked flash attention, grouped matmuls, the combine), about
# 1e-6 of the logits' scale on the CPU; 1e-4 of it leaves that room a
# hundredfold.  q4a8: both round weights, activations and KV alike; an
# activation code that lands on a rounding boundary on one side and not
# the other moves a logit by about one code step, 1e-2 of the scale at
# most here.  Either way a dropped mechanism moves logits by a tenth of
# the scale or more (test below).
TOL = {"f32": 1e-4, "q4a8": 1e-2}


@pytest.mark.parametrize("plan", ["f32", "q4a8"])
def test_engine_paged_decode_matches_reference(params, served, plan):
    prec = F32 if plan == "f32" else Q4A8
    worst, scale = _gap(params, served[plan], M, prec)
    assert worst <= TOL[plan] * scale, (worst, scale)


def _no_renorm(logits, k):
    top, idx = jax.lax.top_k(logits, k)
    return idx, jnp.take_along_axis(jax.nn.softmax(logits, -1), idx, -1)


@pytest.mark.parametrize("drop", ["window", "yarn", "renorm"])
def test_comparison_fails_without_a_mechanism(params, served, drop,
                                              monkeypatch):
    """The reference without the window mask, without YaRN or without
    the top-k renormalization no longer matches the engine."""
    m = dict(M)
    if drop == "window":
        m["window"] = None
    elif drop == "yarn":
        m["yarn_factor"] = None
    else:
        monkeypatch.setattr(ref, "route", _no_renorm)
    worst, scale = _gap(params, served["f32"], m, F32)
    assert worst > 100 * TOL["f32"] * scale, (drop, worst, scale)


def test_submit_accepts_requests_longer_than_the_window(params):
    eng = Engine(params, CFG, EngineConfig(
        batch_size=2, cache_len=64, kv_block_size=4, quantize=False))
    assert eng._clen == 64
    eng.submit(list(range(30)), 30)       # 60 positions, window 8


def test_routed_moe_counts_reach_stats(params):
    runs, eng = _serve(params, None)
    m = eng.stats()["moe"]
    n_dec = eng.decode_iterations
    assert m["layer_steps"] == CFG.n_layers * n_dec
    # every decode step routes top_k pairs per active lane and layer
    assert m["routed_pairs"] == CFG.n_layers * CFG.top_k * eng._decode_tokens
    assert m["experts_touched"] <= m["routed_pairs"]
    assert 1.0 <= m["imbalance_sum"] / m["layer_steps"] <= CFG.top_k


@pytest.mark.parametrize("why,kw", [
    ("ring", {}),
    ("tensor", {"kv_block_size": 4, "tp": 2}),
    ("speculative", {"kv_block_size": 4, "plan": "uniform:4a8,draft=q2:k3",
                     "quantize": True, "group_size": 32}),
])
def test_engine_refuses_what_layer_types_do_not_cover(params, why, kw):
    opts = dict(batch_size=2, cache_len=64, quantize=False)
    opts.update(kw)
    with pytest.raises(ValueError, match="layer_types"):
        Engine(params, CFG, EngineConfig(**opts))


@pytest.mark.parametrize("abits", [None, 8])
def test_routed_moe_equals_dense(abits):
    cfg = CFG
    p = moe.moe_init(jax.random.PRNGKey(3), cfg)
    if abits:
        q, _, _ = quantize_params({"moe": p}, QuantPolicy(
            bits=4, group_size=32, min_size=64, act_bits=abits))
        p = q["moe"]
        assert not isinstance(p["router"], quant.QTensor)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 5, cfg.d_model))
    got, counts = moe.apply_moe_routed(p, x, cfg)
    want, _ = moe.apply_moe_dense(p, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert int(counts.sum()) == 15 * cfg.top_k


def _argmin_codes(w, bits, group):
    """The exhaustive nearest-entry search the quantizer used to run."""
    k, n = w.shape
    wg = w.reshape(k // group, group, n)
    scale = jnp.max(jnp.abs(wg), axis=1)
    scale = jnp.where(scale == 0, 1.0, scale)
    cb = quant._uniform_codebook(bits)
    codes = jnp.argmin(jnp.abs((wg / scale[:, None, :])[..., None] - cb),
                       axis=-1)
    return codes.reshape(k, n)


@pytest.mark.parametrize("bits", [2, 3, 4, 5, 6, 8])
def test_closed_form_codes_equal_the_search(bits):
    rng = np.random.default_rng(bits)
    w = rng.standard_normal((256, 96)).astype(np.float32)
    # values exactly on the grid and halfway between grid points
    qmax = (1 << (bits - 1)) - 1
    w[:64] = (rng.integers(-2 * qmax, 2 * qmax + 1, (64, 96)) / (2 * qmax))
    w[0] = 1.0
    qt = quant.quantize(jnp.asarray(w), bits, group_size=64)
    got = quant.unpack_grouped(qt.packed, bits, 64, 256)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_argmin_codes(w, bits, 64)))


def test_tinymistral_codes_unchanged():
    """The stacked quantizer, slice by slice, gives the search's codes
    on TinyMistral's (smoke) weights."""
    cfg = C.get_smoke("tinymistral_248m")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    qp, _, _ = quantize_params(params, QuantPolicy(bits=4, group_size=32,
                                                   min_size=1024))
    w = params["blocks"]["attn"]["wq"]
    q = qp["blocks"]["attn"]["wq"]
    for layer in range(w.shape[0]):
        got = quant.unpack_grouped(q.packed[layer], 4, 32, w.shape[1])
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(_argmin_codes(w[layer], 4, 32)))


def test_host_leaves_quantize_slice_by_slice():
    """A NumPy tree quantizes to what the same tree on the device gives,
    and every leaf it returns is on the device."""
    rng = np.random.default_rng(0)
    tree = {"blocks": {"moe": {"w_up": rng.standard_normal(
        (2, 3, 64, 32)).astype(np.float32)}},
        "embed": rng.standard_normal((16, 64)).astype(np.float32)}
    pol = QuantPolicy(bits=4, group_size=32, min_size=64)
    host, _, _ = quantize_params(tree, pol)
    dev, _, _ = quantize_params(jax.tree_util.tree_map(jnp.asarray, tree),
                                pol)
    for a, b in zip(jax.tree_util.tree_leaves(host),
                    jax.tree_util.tree_leaves(dev)):
        assert isinstance(a, jax.Array)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_yarn_matches_the_published_numbers():
    """Mellum2's full layers: factor 16 over 8192 positions, beta 32/1,
    the attention factor 0.1 ln 16 + 1; the reference computes the same
    frequencies on its own."""
    from repro.models.layers import yarn_freqs
    cfg = C.get_config("mellum2_12b_a2_5b")
    inv, factor = yarn_freqs(cfg)
    assert factor == pytest.approx(0.1 * np.log(16) + 1, rel=1e-12)
    plain = 1.0 / cfg.rope_theta ** (np.arange(0, 128, 2) / 128)
    assert inv[0] == pytest.approx(plain[0], rel=1e-6)      # extrapolated
    assert inv[-1] == pytest.approx(plain[-1] / 16, rel=1e-6)  # interpolated
    want, scale = ref.rope_freqs(dataclasses.asdict(cfg), "full")
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert scale == pytest.approx(factor)
