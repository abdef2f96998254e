"""Mixed-precision bit allocation: policy resolution, segmented
quantization, per-leaf kernel equivalence at each leaf's precision,
checkpoint round-trips of mixed trees, the greedy budgeted allocator, and
mixed-policy serving."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, strategies as st

import repro.configs as C
from repro.core import sensitivity as sens
from repro.core.quant import SUPPORTED_BITS, quantize
from repro.models import lm
from repro.models.common import ModelConfig
from repro.models.sail_linear import (BitAllocation, QuantPolicy,
                                      QTensor, StackedQTensor,
                                      dequantize_any, quantize_params)
from repro.planning import PlanRule, PlanSpec


def tiny_cfg(**kw):
    base = dict(name="tiny", family="dense", vocab=64, d_model=32,
                n_layers=2, n_heads=4, n_kv=2, d_ff=64, act="swiglu",
                attn_chunk=16, max_seq=128)
    base.update(kw)
    return ModelConfig(**base)


def tiny_params(cfg=None, seed=0):
    return lm.init_params(jax.random.PRNGKey(seed), cfg or tiny_cfg())


POLICY = dict(group_size=32, min_size=1024)


def iter_qtensors(tree, prefix=""):
    """(path, QTensor|StackedQTensor) leaves of a quantized tree."""
    if isinstance(tree, (QTensor, StackedQTensor)):
        yield prefix, tree
    elif isinstance(tree, dict):
        for k, v in tree.items():
            yield from iter_qtensors(v, prefix + f"['{k}']")
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from iter_qtensors(v, prefix + f"[{i}]")


# ---------------------------------------------------------------------------
# policy resolution
# ---------------------------------------------------------------------------

def test_bits_for_precedence():
    alloc = BitAllocation(per_path={"['x']": 6, "['y']": (2, 8)})
    pol = QuantPolicy(bits=4, rules=(("x", 3),), allocation=alloc)
    assert pol.bits_for("['x']") == 3          # rules beat allocation
    assert pol.bits_for("['y']") == (2, 8)     # allocation beats default
    assert pol.bits_for("['z']") == 4          # fallback


def test_policy_spec_roundtrip():
    alloc = BitAllocation(per_path={"['a']": 5, "['b']": (2, 3, 4)})
    pol = QuantPolicy(bits=6, group_size=64, min_size=2048,
                      rules=(("mlp", 3),), allocation=alloc)
    spec = pol.to_spec()
    back = QuantPolicy.from_spec(spec)
    assert back == pol
    # specs are msgpack/JSON-plain
    import json
    json.dumps(spec)


def test_parse_bit_policy_grammar():
    u = PlanSpec.parse("uniform:6")
    assert (u.mode, u.weight_bits, u.act_bits) == ("uniform", 6, None)
    r = PlanSpec.parse("rules:attn=5,mlp=3,default=4")
    assert r.mode == "rules" and r.weight_bits == 4
    assert PlanRule("attn", 5) in r.rules and PlanRule("mlp", 3) in r.rules
    a = PlanSpec.parse("auto:q4")
    assert (a.mode, a.weight_bits, a.budget_bpw) == ("auto", 4, None)
    b = PlanSpec.parse("auto:4.5bpw")
    assert (b.mode, b.budget_bpw) == ("auto", 4.5)
    with pytest.raises(ValueError):
        PlanSpec.parse("nope:1")


def test_unsupported_bits_rejected():
    pol = QuantPolicy(bits=4, rules=(("wq", 7),), **POLICY)
    with pytest.raises(ValueError):
        quantize_params(tiny_params(), pol)


# ---------------------------------------------------------------------------
# mixed quantize_params
# ---------------------------------------------------------------------------

def test_mixed_leaf_bits_and_bytes():
    params = tiny_params()
    pol = QuantPolicy(bits=4, rules=(("mlp", 2), ("wo", 8)), **POLICY)
    qtree, b0, b1 = quantize_params(params, pol)
    bits = {path: qt.bits for path, qt in iter_qtensors(qtree)}
    assert bits["['blocks']['mlp']['w_down']"] == 2
    assert bits["['blocks']['attn']['wo']"] == 8
    assert bits["['blocks']['attn']['wq']"] == 4
    _, _, uniform4 = quantize_params(params, QuantPolicy(bits=4, **POLICY))
    _, _, uniform2 = quantize_params(params, QuantPolicy(bits=2, **POLICY))
    assert uniform2 < b1 < uniform4 + (1 << 8) * 4


def test_per_layer_allocation_segments_blocks():
    params = tiny_params()
    alloc = BitAllocation(per_path={"['blocks']['attn']['wq']": (8, 2)})
    qtree, _, _ = quantize_params(
        params, QuantPolicy(bits=4, allocation=alloc, **POLICY))
    assert isinstance(qtree["blocks"], list) and len(qtree["blocks"]) == 2
    assert qtree["blocks"][0]["attn"]["wq"].bits == 8
    assert qtree["blocks"][1]["attn"]["wq"].bits == 2
    assert qtree["blocks"][0]["mlp"]["w_up"].bits == 4
    # each segment slice dequantizes to the per-slice quantization of the
    # original weight at that slice's bits
    w = params["blocks"]["attn"]["wq"]
    for seg, layer, bits in ((0, 0, 8), (1, 1, 2)):
        got = dequantize_any(qtree["blocks"][seg]["attn"]["wq"])[0]
        want = sens.fake_quant(w[layer], bits, 32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_uniform_tuple_does_not_segment():
    params = tiny_params()
    alloc = BitAllocation(per_path={"['blocks']['attn']['wq']": (6, 6)})
    qtree, _, _ = quantize_params(
        params, QuantPolicy(bits=4, allocation=alloc, **POLICY))
    assert isinstance(qtree["blocks"], dict)
    assert qtree["blocks"]["attn"]["wq"].bits == 6


def test_segmented_model_matches_dequantized_oracle():
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    alloc = BitAllocation(per_path={
        "['blocks']['attn']['wq']": (8, 4),
        "['blocks']['mlp']['w_down']": (4, 8),
    })
    qtree, _, _ = quantize_params(
        params, QuantPolicy(bits=4, allocation=alloc, **POLICY))
    assert isinstance(qtree["blocks"], list)
    # oracle: same tree with every QTensor dequantized back to f32 arrays,
    # segments re-stacked into one scan
    deq_segs = [jax.tree_util.tree_map(
        dequantize_any, seg,
        is_leaf=lambda x: isinstance(x, (QTensor, StackedQTensor)))
        for seg in qtree["blocks"]]
    oracle = {k: v for k, v in qtree.items() if k != "blocks"}
    oracle = jax.tree_util.tree_map(
        dequantize_any, oracle,
        is_leaf=lambda x: isinstance(x, (QTensor, StackedQTensor)))
    oracle["blocks"] = jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, 0), *deq_segs)

    toks = jnp.asarray([[1, 2, 3, 4]])
    lq, cq = lm.prefill(qtree, toks, cfg, cache_len=16)
    lo, co = lm.prefill(oracle, toks, cfg, cache_len=16)
    np.testing.assert_allclose(np.asarray(lq), np.asarray(lo), rtol=1e-5,
                               atol=1e-5)
    tok = jnp.argmax(lq, axis=-1)[:, None]
    for _ in range(3):
        lq, cq = lm.decode_step(qtree, tok, cq, cfg)
        lo, co = lm.decode_step(oracle, tok, co, cfg)
        np.testing.assert_allclose(np.asarray(lq), np.asarray(lo),
                                   rtol=1e-5, atol=1e-5)
        tok = jnp.argmax(lq, axis=-1)[:, None]


# ---------------------------------------------------------------------------
# per-leaf kernel equivalence (property)
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(b_mlp=st.sampled_from(SUPPORTED_BITS),
       b_attn=st.sampled_from(SUPPORTED_BITS), seed=st.integers(0, 99))
def test_property_mixed_leaves_match_uniform_reference(b_mlp, b_attn, seed):
    """Every leaf of a mixed tree must equal the uniform-quantized tensor
    at that leaf's precision, and ``lut_matmul`` on it must match the
    pure-jnp reference at that precision (kernel dispatch is per-tensor,
    so mixing cannot change any single matmul's numerics)."""
    from repro.kernels.lut_gemv.ops import lut_matmul
    from repro.kernels.lut_gemv.ref import lut_matmul_ref
    params = tiny_params(seed=seed)
    pol = QuantPolicy(bits=4, rules=(("mlp", b_mlp), ("attn", b_attn)),
                      **POLICY)
    qtree, _, _ = quantize_params(params, pol)
    raw = {p: w for p, w in
           ((jax.tree_util.keystr(path), w) for path, w in
            jax.tree_util.tree_flatten_with_path(params)[0])}
    rng = np.random.default_rng(seed)
    for path, qt in iter_qtensors(qtree):
        w = raw[path]
        expect_bits = b_mlp if "mlp" in path else (
            b_attn if "attn" in path else 4)
        assert qt.bits == expect_bits, path
        if isinstance(qt, StackedQTensor):
            qt = qt[0]
            w = w[0]
        ref_qt = quantize(w, expect_bits, 32)
        np.testing.assert_array_equal(np.asarray(qt.packed),
                                      np.asarray(ref_qt.packed))
        x = jnp.asarray(rng.standard_normal((3, qt.k)), jnp.float32)
        y_kernel = lut_matmul(x, qt, backend="pallas")
        y_ref = lut_matmul_ref(x, ref_qt)
        np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# checkpoint round-trip (property)
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(b_a=st.sampled_from(SUPPORTED_BITS),
       b_b=st.sampled_from(SUPPORTED_BITS),
       b_l0=st.sampled_from([2, 4, 8]), b_l1=st.sampled_from([2, 4, 8]))
def test_property_checkpoint_roundtrip_mixed(b_a, b_b, b_l0, b_l1):
    """A mixed-bits tree (incl. per-layer segmentation) must round-trip
    through save/load bit-exactly, both against its own template and
    rebuilt from nothing but the raw params via the stored policy spec."""
    from repro.checkpoint import restore, restore_quantized, save_quantized
    params = tiny_params()
    alloc = BitAllocation(per_path={
        "['blocks']['attn']['wq']": (b_l0, b_l1),
        "['blocks']['mlp']['w_up']": b_a,
        "['lm_head']": b_b,
    })
    pol = QuantPolicy(bits=4, allocation=alloc, **POLICY)
    qtree, _, _ = quantize_params(params, pol)
    with tempfile.TemporaryDirectory() as d:
        save_quantized(d, 1, qtree, pol)
        back, _ = restore(d, qtree)
        flat_a = jax.tree_util.tree_leaves(qtree)
        flat_b = jax.tree_util.tree_leaves(back)
        assert len(flat_a) == len(flat_b)
        for a, b in zip(flat_a, flat_b):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # template-free restore: quantized structure (statics, segments)
        # reconstructed from the manifest's policy spec
        back2, _ = restore_quantized(d, params)
        bits_orig = {p: q.bits for p, q in iter_qtensors(qtree)}
        bits_back = {p: q.bits for p, q in iter_qtensors(back2)}
        assert bits_orig == bits_back
        for a, b in zip(flat_a, jax.tree_util.tree_leaves(back2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------

def make_units(n=6, k=64, seed=0):
    rng = np.random.default_rng(seed)
    units = []
    for i in range(n):
        scale = float(rng.uniform(0.1, 10.0))
        errors = {b: scale * 4.0 ** (-b) for b in SUPPORTED_BITS}
        units.append(sens.Unit(path=f"['w{i}']", layer=None, k=k, n=k,
                               copies=1, errors=errors))
    return units


def test_allocator_respects_budget_and_dominates_uniform():
    units = make_units()
    g = 32
    for b in (3, 4, 5):
        budget = sum(sens.unit_bytes(u.k, u.n, b, g, u.copies)
                     for u in units)
        rep = sens.allocate_bits(units, budget, g)
        assert rep.feasible and rep.bytes_total <= budget
        uniform_err = sum(u.errors[b] for u in units)
        assert rep.predicted_error <= uniform_err + 1e-12


def test_allocator_monotone_in_budget():
    units = make_units(seed=1)
    g = 32
    budgets = [sum(sens.unit_bytes(u.k, u.n, b, g, u.copies)
                   for u in units) for b in (2, 3, 4, 6, 8)]
    errs = [sens.allocate_bits(units, bb, g).predicted_error
            for bb in budgets]
    assert all(e2 <= e1 + 1e-12 for e1, e2 in zip(errs, errs[1:]))


def test_allocator_pins_rule_matched_units():
    units = make_units()
    g = 32
    budget = sum(sens.unit_bytes(u.k, u.n, 4, g, u.copies) for u in units)
    rep = sens.allocate_bits(units, budget, g,
                             pinned={("['w0']", None): 8})
    assert rep.bits_by_unit[("['w0']", None)] == 8


def test_allocator_infeasible_budget_reports():
    units = make_units(n=2)
    rep = sens.allocate_bits(units, budget_bytes=8, group_size=32)
    assert not rep.feasible
    assert all(b == min(SUPPORTED_BITS) for b in rep.bits_by_unit.values())


# ---------------------------------------------------------------------------
# sensitivity scoring
# ---------------------------------------------------------------------------

def test_output_sensitivity_decreases_with_bits():
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    pol = QuantPolicy(bits=4, **POLICY)
    toks = sens.calibration_tokens(cfg.vocab, 2, 16)
    scores = sens.output_sensitivity(params, cfg, toks, pol,
                                     bits_candidates=(2, 4, 8))
    assert scores, "no quantizable units found"
    for key, errs in scores.items():
        assert errs[8] <= errs[2] + 1e-9, key
    # per-layer granularity over the stacked blocks
    layers = {k[1] for k in scores if k[0].startswith("['blocks']")}
    assert layers == {0, 1}


def test_weight_sensitivity_proxy_decreases_with_bits():
    params = tiny_params()
    pol = QuantPolicy(bits=4, **POLICY)
    scores = sens.weight_sensitivity(params, pol, bits_candidates=(2, 4, 8))
    for key, errs in scores.items():
        assert errs[8] <= errs[4] <= errs[2] + 1e-9, key


def test_calibrate_policy_matches_budget():
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    base = QuantPolicy(bits=4, **POLICY)
    toks = sens.calibration_tokens(cfg.vocab, 2, 16)
    pol, rep = sens.calibrate_policy(params, cfg, base, match_uniform=4,
                                     tokens=toks,
                                     bits_candidates=(2, 3, 4, 6))
    assert rep.feasible
    assert rep.bytes_total <= rep.budget_bytes
    assert pol.allocation is not None
    # the allocated policy must actually quantize (and possibly segment)
    qtree, _, _ = quantize_params(params, pol)
    assert dict(iter_qtensors(qtree))


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

def test_engine_mixed_plan_matches_reference_and_f32_logits():
    """A high-precision mixed allocation (per-layer 6/8 bits -> segmented
    serving path) serves exactly the plain reference loop's greedy tokens
    over the same quantized weights, and its logits stay within 6-bit
    rounding of the unquantized model's."""
    from repro.serving.engine import Engine, EngineConfig
    cfg = C.get_smoke("tinymistral_248m")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [[1, 2, 3], [4, 5, 6, 7], [8, 9]]
    alloc = BitAllocation(
        per_path={"['blocks']['mlp']['w_down']": (6, 8)})
    pol = QuantPolicy(bits=8, group_size=32, min_size=1024,
                      allocation=alloc)
    eng = Engine(params, cfg, EngineConfig(
        batch_size=4, cache_len=64, quantize=True, ql=8, group_size=32,
        quant_kv=False, plan=PlanSpec.from_policy(pol, quant_kv=False)))
    assert isinstance(eng.params["blocks"], list), \
        "per-layer allocation must serve through the segmented path"
    assert eng.stats()["mixed_precision"]
    uids = [eng.submit(p, max_new_tokens=8) for p in prompts]
    served = {c.uid: c.tokens for c in eng.run()}
    # like with like: the reference loop over the engine's own weights
    for uid, p in zip(uids, prompts):
        ref = lm.greedy_generate(eng.params, np.asarray([p], np.int32),
                                 cfg, 8, cache_len=64)
        assert served[uid] == np.asarray(ref[0]).tolist(), p
    # against f32 only on logits: greedy tokens of a random-init model
    # sit near ties, so one rounding can flip an argmax.  A b-bit code
    # rounds each weight by at most 1/(2^b - 1) of its group's absmax;
    # 6 bits is the coarsest here, so each of the model's matmuls (7 per
    # layer, plus lm_head) carries at most 1/63 relative error, and the
    # independent errors add in quadrature.
    tol = np.sqrt(7 * cfg.n_layers + 1) / (2 ** 6 - 1)
    for uid, p in zip(uids, prompts):
        seq = jnp.asarray([p + served[uid]], jnp.int32)
        want, _ = lm.forward(params, seq, cfg)
        got, _ = lm.forward(eng.params, seq, cfg)
        err = jnp.linalg.norm(got - want) / jnp.linalg.norm(want)
        assert float(err) <= tol, (p, float(err), tol)


def test_engine_auto_bit_policy_smoke():
    """auto:q4 runs the sensitivity calibration inside the engine and
    serves with a budget-respecting mixed allocation."""
    from repro.serving.engine import Engine, EngineConfig
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    eng = Engine(params, cfg, EngineConfig(
        batch_size=2, cache_len=32, quantize=True, ql=4, group_size=32,
        quant_kv=True, plan="auto:q4"))
    assert eng.quant_policy.allocation is not None
    eng.submit([1, 2, 3], max_new_tokens=4)
    done = eng.run()
    assert len(done) == 1 and len(done[0].tokens) == 4
    # allocation bytes within the uniform-4 budget
    budget = sens.uniform_bytes(params, eng.quant_policy, 4)
    used = 0
    for pstr, w, stacked in sens.quantizable_units(params,
                                                   eng.quant_policy):
        spec = eng.quant_policy.bits_for(pstr)
        k, n = int(w.shape[-2]), int(w.shape[-1])
        copies = 1
        for d in w.shape[:-2]:
            copies *= int(d)
        if isinstance(spec, (tuple, list)):
            per = copies // len(spec)
            used += sum(sens.unit_bytes(k, n, int(b), 32, per)
                        for b in spec)
        else:
            used += sens.unit_bytes(k, n, int(spec), 32, copies)
    assert used <= budget


def test_engine_rules_bit_policy_string():
    from repro.serving.engine import Engine, EngineConfig
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    eng = Engine(params, cfg, EngineConfig(
        batch_size=2, cache_len=32, quantize=True, ql=4, group_size=32,
        plan="rules:mlp=2,default=6"))
    bits = {p: q.bits for p, q in iter_qtensors(eng.params)}
    assert bits["['blocks']['mlp']['w_up']"] == 2
    assert bits["['blocks']['attn']['wq']"] == 6
    eng.submit([3, 2, 1], max_new_tokens=3)
    assert len(eng.run()) == 1
