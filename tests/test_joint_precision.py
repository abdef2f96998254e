"""Joint (wbits, abits) allocation: policy grammar and spec round-trips,
activation-quantized serving numerics, exact-centered activation probes,
the product-grid solver, real-calibration-data hooks, the scan-segment
cap (compile-cost regression), and checkpoint round-trips of
activation-allocated trees."""
import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, strategies as st

from repro.core import cost_model as cm
from repro.core import sensitivity as sens
from repro.core.quant import SUPPORTED_ABITS, quantize
from repro.models import lm
from repro.models.common import ModelConfig
from repro.models.sail_linear import (BitAllocation, QuantPolicy, QTensor,
                                      StackedQTensor, act_fake_quant,
                                      mm, quantize_params)
from repro.planning import PlanRule, PlanSpec, resolve_plan


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
    if isinstance(tree, (QTensor, StackedQTensor)):
        yield prefix, tree
    elif isinstance(tree, dict):
        for k, v in tree.items():
            yield from iter_qtensors(v, prefix + f"['{k}']")
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from iter_qtensors(v, prefix + f"[{i}]")


# ---------------------------------------------------------------------------
# grammar + spec round-trips
# ---------------------------------------------------------------------------

def test_parse_bit_policy_activation_grammar():
    assert PlanSpec.parse("uniform:4a8") == PlanSpec(
        mode="uniform", weight_bits=4, act_bits=8)
    r = PlanSpec.parse("rules:attn=5a6,mlp=3,default=4a8")
    assert r.rules == (PlanRule("attn", 5, 6), PlanRule("mlp", 3))
    assert r.weight_bits == 4 and r.act_bits == 8
    a = PlanSpec.parse("auto:q4a8,prt=measured,maxseg=2")
    assert a == PlanSpec(mode="auto", weight_bits=4, act_bits=8,
                         prt="measured", max_segments=2)
    # weight-only forms carry no activation precision
    assert PlanSpec.parse("auto:q4") == PlanSpec(mode="auto",
                                                 weight_bits=4)
    assert PlanSpec.parse("uniform:6") == PlanSpec(mode="uniform",
                                                   weight_bits=6)
    with pytest.raises(ValueError):
        PlanSpec.parse("auto:q4a8,prt=sometimes")
    with pytest.raises(ValueError):
        PlanSpec.parse("uniform:4b8")


def test_policy_spec_roundtrip_with_activations():
    alloc = BitAllocation(per_path={"['a']": 5, "['b']": (2, 3)},
                          act_per_path={"['a']": 8, "['b']": (4, 6)})
    pol = QuantPolicy(bits=6, group_size=64, min_size=2048,
                      allocation=alloc, act_bits=8,
                      act_rules=(("head", 6),))
    back = QuantPolicy.from_spec(pol.to_spec())
    assert back == pol
    import json
    json.dumps(pol.to_spec())
    # legacy flat allocation specs still parse (weight-only checkpoints)
    legacy = BitAllocation.from_spec({"['x']": 4, "['y']": [2, 8]})
    assert legacy.per_path["['y']"] == (2, 8)
    assert legacy.lookup_act("['x']") is None


def test_abits_precedence_and_validation():
    alloc = BitAllocation(per_path={}, act_per_path={"['y']": 6})
    pol = QuantPolicy(bits=4, act_bits=8, act_rules=(("x", 4),),
                      allocation=alloc)
    assert pol.abits_for("['x']") == 4      # act_rules beat allocation
    assert pol.abits_for("['y']") == 6      # allocation beats fallback
    assert pol.abits_for("['z']") == 8      # fallback
    assert QuantPolicy(bits=4).abits_for("['z']") is None
    bad = QuantPolicy(bits=4, act_rules=(("x", 5),))
    with pytest.raises(ValueError):
        bad.abits_for("['x']")


def test_resolve_bit_policy_activation_strings():
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    base = QuantPolicy(bits=4, **POLICY)
    uni = resolve_plan("uniform:6a8", params, cfg, base=base).policy
    assert uni.bits == 6 and uni.act_bits == 8
    rules = resolve_plan("rules:mlp=4a6,default=6a8", params, cfg,
                         base=base).policy
    assert rules.act_rules == (("mlp", 6),) and rules.act_bits == 8


# ---------------------------------------------------------------------------
# activation-quantized serving numerics
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(abits=st.sampled_from(SUPPORTED_ABITS), bits=st.sampled_from([2, 4, 8]),
       seed=st.integers(0, 99))
def test_property_mm_applies_activation_quant(abits, bits, seed):
    """mm on a QTensor carrying abits must run the integer-activation
    matmul, equal the same matmul on explicitly fake-quantized
    activations up to float32 rounding — and differ from the f32 path
    whenever quantization actually rounds."""
    from repro.core.quant import dequantize, quantize_activations
    from repro.kernels.lut_gemv.ops import lut_matmul
    from repro.kernels.lut_gemv.ref import lut_matmul_ref_int
    rng = np.random.default_rng(seed)
    k = 64
    w = jnp.asarray(rng.standard_normal((k, 16)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((3, k)), jnp.float32)
    qt = quantize(w, bits, 32)
    qta = dataclasses.replace(qt, abits=int(abits))
    got = mm(x, qta)
    # like with like: the integer-code oracle, scale applied after the dot
    x_q, x_scale = quantize_activations(x, abits)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(lut_matmul_ref_int(x_q, x_scale, qt)),
        rtol=1e-5, atol=1e-6)
    # fake-quant folds the scale in before the dot: mathematically equal,
    # not bitwise.  Each side is a K-term float32 dot plus one scale
    # product, so each lies within (K + 2) * eps * sum|x_i * w_i| of the
    # exact value (the standard dot-product rounding bound) and the two
    # within twice that; a bound relative to |want| fails where the sum
    # cancels to near zero.
    fq = act_fake_quant(x, abits)
    want = lut_matmul(fq, qt, backend="jnp")
    bound = 2 * (k + 2) * np.finfo(np.float32).eps * (
        jnp.abs(fq) @ jnp.abs(dequantize(qt)))
    assert bool(jnp.all(jnp.abs(got - want) <= bound))
    f32 = mm(x, qt)
    assert float(jnp.max(jnp.abs(got - f32))) > 0.0


def test_quantized_leaves_carry_abits_and_segment():
    params = tiny_params()
    alloc = BitAllocation(per_path={},
                          act_per_path={"['blocks']['attn']['wq']": (8, 4)})
    pol = QuantPolicy(bits=4, allocation=alloc, act_bits=8, **POLICY)
    qtree, _, _ = quantize_params(params, pol)
    # abits-only change segments the stack exactly like weight bits do
    assert isinstance(qtree["blocks"], list) and len(qtree["blocks"]) == 2
    assert qtree["blocks"][0]["attn"]["wq"].abits == 8
    assert qtree["blocks"][1]["attn"]["wq"].abits == 4
    assert qtree["blocks"][0]["attn"]["wq"].bits == 4
    assert qtree["blocks"][1]["mlp"]["w_up"].abits == 8  # act_bits fallback


def test_act_quantized_model_close_to_f32_activations():
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    toks = jnp.asarray([[1, 2, 3, 4]])
    base = QuantPolicy(bits=8, **POLICY)
    ref = lm.forward(quantize_params(params, base)[0], toks, cfg)[0]
    a8 = dataclasses.replace(base, act_bits=8)
    got = lm.forward(quantize_params(params, a8)[0], toks, cfg)[0]
    err = float(jnp.mean((got - ref) ** 2))
    assert 0.0 < err < 1e-3   # 8-bit activations: small but nonzero noise


# ---------------------------------------------------------------------------
# activation sensitivity probes
# ---------------------------------------------------------------------------

def test_activation_sensitivity_centered_and_ordered():
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    pol = QuantPolicy(bits=4, **POLICY)
    toks = sens.calibration_tokens(cfg.vocab, 2, 16)
    scores = sens.activation_sensitivity(params, cfg, toks, pol,
                                         abits_candidates=(4, 8))
    assert scores, "no quantizable units found"
    base = {errs[None] for errs in scores.values()}
    assert len(base) == 1          # every probe shares the exact center
    for key, errs in scores.items():
        # exact-centered probes interact with the quantized-weight center,
        # so a single unit may see tiny inversions — bound them to noise
        assert errs[4] >= errs[8] - 1e-3, key
    total4 = sum(errs[4] for errs in scores.values())
    total8 = sum(errs[8] for errs in scores.values())
    assert total4 > total8         # 4-bit activations hurt in aggregate
    layers = {k[1] for k in scores if k[0].startswith("['blocks']")}
    assert layers == {0, 1}


# ---------------------------------------------------------------------------
# joint allocator
# ---------------------------------------------------------------------------

def make_joint_units(n=5, k=64, seed=0):
    rng = np.random.default_rng(seed)
    units = []
    for i in range(n):
        ws = float(rng.uniform(0.1, 10.0))
        asc = float(rng.uniform(0.01, 1.0))
        units.append(sens.Unit(
            path=f"['w{i}']", layer=None, k=k, n=k, copies=1,
            errors={b: ws * 4.0 ** (-b) for b in (2, 3, 4, 5, 6, 8)},
            aerrors={ab: asc * 2.0 ** (-ab) for ab in SUPPORTED_ABITS}))
    return units


def uniform_cycles(units, wb, ab):
    return cm.mixed_decode_cycles(
        [(u.k, u.n, wb, ab, u.copies) for u in units], nbw="auto")


def test_joint_allocator_beats_uniform_within_cycle_budget():
    units = make_joint_units()
    budget = uniform_cycles(units, 4, 8)
    rep = sens.allocate_bits_joint(units, budget, group_size=32)
    assert rep.feasible
    assert rep.cycles_total <= budget * (1 + 1e-9)
    uniform_err = sum(u.errors[4] + u.aerrors[8] for u in units)
    assert rep.predicted_error <= uniform_err + 1e-12
    for wb, ab in rep.bits_by_unit.values():
        assert wb in (2, 3, 4, 5, 6, 8) and ab in SUPPORTED_ABITS


def test_joint_allocator_byte_budget_and_pins():
    units = make_joint_units(seed=3)
    budget = uniform_cycles(units, 6, 8)
    byte_budget = sum(sens.unit_bytes(u.k, u.n, 4, 32, u.copies)
                      for u in units)
    rep = sens.allocate_bits_joint(
        units, budget, group_size=32, byte_budget=byte_budget,
        pinned={("['w0']", None): 8}, pinned_act={("['w1']", None): 4})
    assert rep.bytes_total <= byte_budget
    assert rep.bits_by_unit[("['w0']", None)][0] == 8
    assert rep.bits_by_unit[("['w1']", None)][1] == 4


def test_joint_allocator_infeasible_budget_reports():
    units = make_joint_units(n=2)
    rep = sens.allocate_bits_joint(units, cycle_budget=1.0, group_size=32)
    assert not rep.feasible


def test_joint_allocator_requires_act_scores():
    u = sens.Unit(path="['w']", layer=None, k=64, n=64, copies=1,
                  errors={b: 1.0 for b in (2, 4, 8)})
    with pytest.raises(ValueError):
        sens.allocate_bits_joint([u], 1e9, group_size=32)


def test_calibrate_policy_joint_end_to_end():
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    base = QuantPolicy(bits=4, **POLICY)
    toks = sens.calibration_tokens(cfg.vocab, 2, 16)
    pol, rep = sens.calibrate_policy(
        params, cfg, base, match_uniform=4, tokens=toks,
        bits_candidates=(2, 4, 6, 8), abits_candidates=(4, 8))
    assert rep.feasible
    assert rep.cycles_total <= rep.cycle_budget * (1 + 1e-9)
    assert pol.allocation is not None and pol.allocation.act_per_path
    qtree, _, _ = quantize_params(params, pol)
    abits_seen = {qt.abits for _, qt in iter_qtensors(qtree)}
    assert abits_seen <= {4, 8}
    toks2 = jnp.asarray([[1, 2, 3]])
    logits, _ = lm.prefill(qtree, toks2, cfg, cache_len=8)
    assert np.isfinite(np.asarray(logits)).all()


# ---------------------------------------------------------------------------
# real-calibration-data hook
# ---------------------------------------------------------------------------

def test_tokens_from_calib_batches():
    a = np.zeros((2, 8), np.int32)
    b = np.ones((3, 8), np.int32)
    toks = sens._tokens_from_calib_batches([a, b])
    assert toks.shape == (5, 8)
    with pytest.raises(ValueError):
        sens._tokens_from_calib_batches([a, np.ones((2, 4), np.int32)])


def test_calibrate_policy_uses_heldout_batches():
    """The allocation must respond to the calibration data distribution:
    held-out batches concentrated on a few tokens vs broad random text
    probe different activation paths and move bits."""
    cfg = tiny_cfg(n_layers=2)
    params = tiny_params(cfg, seed=1)
    base = QuantPolicy(bits=4, **POLICY)
    narrow = [np.full((4, 16), 3, np.int32)]
    broad = [np.asarray(jax.random.randint(jax.random.PRNGKey(s),
                                           (4, 16), 0, cfg.vocab))
             for s in (0, 1)]
    pol_n, rep_n = sens.calibrate_policy(params, cfg, base,
                                         match_uniform=3,
                                         calib_batches=narrow,
                                         bits_candidates=(2, 3, 4, 6))
    pol_b, rep_b = sens.calibrate_policy(params, cfg, base,
                                         match_uniform=3,
                                         calib_batches=broad,
                                         bits_candidates=(2, 3, 4, 6))
    assert rep_n.feasible and rep_b.feasible
    assert rep_n.bits_by_unit != rep_b.bits_by_unit


# ---------------------------------------------------------------------------
# segment cap (compile-cost regression)
# ---------------------------------------------------------------------------

def scan_count(qtree, cfg, toks):
    """Number of lax.scan bodies the forward compiles — one per segment,
    each a separately traced/compiled computation."""
    jaxpr = jax.make_jaxpr(lambda p: lm.forward(p, toks, cfg)[0])(qtree)
    return sum(1 for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan")


def test_segment_count_drives_compiled_scan_bodies():
    cfg = tiny_cfg(n_layers=4)
    params = tiny_params(cfg)
    toks = jnp.asarray([[1, 2, 3]])
    counts = {}
    for name, spec in {1: (4, 4, 4, 4), 2: (4, 4, 8, 8),
                       4: (4, 8, 4, 8)}.items():
        alloc = BitAllocation(
            per_path={"['blocks']['attn']['wq']": spec})
        qtree, _, _ = quantize_params(
            params, QuantPolicy(bits=4, allocation=alloc, **POLICY))
        counts[name] = scan_count(qtree, cfg, toks)
    # trace/compile cost grows linearly with segment count — the
    # regression the allocator's max_segments cap exists to bound
    assert counts == {1: 1, 2: 2, 4: 4}


def test_enforce_max_segments_cap_and_losslessness():
    units = []
    for p in ("['blocks']['a']", "['blocks']['b']"):
        for layer in range(4):
            units.append(sens.Unit(
                path=p, layer=layer, k=64, n=64, copies=1,
                errors={b: (layer + 1) * 4.0 ** (-b)
                        for b in (2, 4, 6, 8)}))
    # 3 natural segments: [0], [1, 2], [3]
    assign = {("['blocks']['a']", 0): 2, ("['blocks']['a']", 1): 4,
              ("['blocks']['a']", 2): 4, ("['blocks']['a']", 3): 6,
              ("['blocks']['b']", 0): 4, ("['blocks']['b']", 1): 4,
              ("['blocks']['b']", 2): 4, ("['blocks']['b']", 3): 4}
    assert sens.segment_count(assign) == 3
    # cap >= natural count: lossless identity
    same = sens.enforce_max_segments(units, assign, 3)
    assert same == assign
    # tighter cap: merged, within cap, every value adopted from the
    # original assignment of an adjacent segment (never invented)
    capped = sens.enforce_max_segments(units, assign, 2)
    assert sens.segment_count(capped) <= 2
    assert set(capped) == set(assign)
    for (p, layer), b in capped.items():
        assert b in {assign[(p, i)] for i in range(4)}


def test_max_segments_validated():
    with pytest.raises(ValueError, match="maxseg"):
        PlanSpec.parse("auto:q4a8,maxseg=0")
    with pytest.raises(ValueError, match="max_segments"):
        sens.enforce_max_segments([], {}, 0)


def test_calibrate_policy_max_segments():
    cfg = tiny_cfg(n_layers=4)
    params = tiny_params(cfg)
    base = QuantPolicy(bits=4, **POLICY)
    toks = sens.calibration_tokens(cfg.vocab, 2, 16)
    scores = sens.output_sensitivity(params, cfg, toks, base,
                                     bits_candidates=(2, 3, 4, 6))
    free, rep_free = sens.calibrate_policy(
        params, cfg, base, match_uniform=4, scores=scores,
        bits_candidates=(2, 3, 4, 6))
    capped, rep_cap = sens.calibrate_policy(
        params, cfg, base, match_uniform=4, scores=scores,
        bits_candidates=(2, 3, 4, 6), max_segments=2)
    assert sens.segment_count(rep_cap.bits_by_unit) <= 2
    assert rep_cap.predicted_error >= rep_free.predicted_error - 1e-12
    # the report must stay honest after capping: feasible only if the
    # coalesced assignment still fits the budget it was solved under
    assert rep_cap.feasible == (rep_cap.bytes_total
                                <= rep_cap.budget_bytes)
    qtree, _, _ = quantize_params(params, capped)
    segs = (len(qtree["blocks"])
            if isinstance(qtree["blocks"], list) else 1)
    assert segs <= 2


def test_calibrate_policy_joint_enforces_bpw_byte_budget():
    """A budget_bpw request is an explicit byte budget: joint mode must
    enforce it, not silently allocate unbounded bytes."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    base = QuantPolicy(bits=4, **POLICY)
    toks = sens.calibration_tokens(cfg.vocab, 2, 16)
    scores = sens.output_sensitivity(params, cfg, toks, base,
                                     bits_candidates=(2, 4, 8))
    act_scores = sens.activation_sensitivity(params, cfg, toks, base,
                                             abits_candidates=(4, 8))
    pol, rep = sens.calibrate_policy(
        params, cfg, base, budget_bpw=3.0, scores=scores,
        act_scores=act_scores, bits_candidates=(2, 4, 8),
        abits_candidates=(4, 8))
    assert rep.byte_budget is not None
    assert rep.bytes_total <= rep.byte_budget


def test_measured_prt_calibration_uses_embedding_activations():
    """With prt="measured" and calibration tokens, the hit rates must be
    simulated on the tokens' embedding activations (the real-data
    stand-in), not the fixed synthetic batch — the discount responds to
    the model/data instead of being a global constant."""
    from repro.core import pattern
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    toks = sens.calibration_tokens(cfg.vocab, 2, 8)
    emb = np.asarray(jnp.take(params["embed"], toks, axis=0), np.float32)
    emb = emb.reshape(-1, emb.shape[-1])[:8]
    d_emb = pattern.prt_discount(2, 8, 4, emb)
    d_syn = pattern.prt_discount(2, 8, 4, None)
    assert d_emb != d_syn   # distinct data -> distinct measured discount
    assert 0.0 <= d_emb <= 1.0


def test_calibrate_policy_joint_rejects_weight_mode():
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    base = QuantPolicy(bits=4, **POLICY)
    with pytest.raises(ValueError, match="mode='output'"):
        sens.calibrate_policy(params, cfg, base, mode="weight",
                              abits_candidates=(4, 8))


def test_calibrate_policy_weight_only_rejects_measured_prt():
    """prt= shapes the joint cycle budget only; a weight-only call must
    fail loudly instead of silently ignoring the requested pricing."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    base = QuantPolicy(bits=4, **POLICY)
    with pytest.raises(ValueError, match="joint"):
        sens.calibrate_policy(params, cfg, base, prt="measured")


# ---------------------------------------------------------------------------
# checkpoint round-trip with activation allocation
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_activation_allocation():
    from repro.checkpoint import restore_quantized, save_quantized
    params = tiny_params()
    alloc = BitAllocation(
        per_path={"['blocks']['attn']['wq']": (6, 4)},
        act_per_path={"['blocks']['attn']['wq']": (8, 4),
                      "['blocks']['mlp']['w_up']": 6})
    pol = QuantPolicy(bits=4, allocation=alloc, act_bits=8, **POLICY)
    qtree, _, _ = quantize_params(params, pol)
    with tempfile.TemporaryDirectory() as d:
        save_quantized(d, 1, qtree, pol)
        back, _ = restore_quantized(d, params)
        orig = {p: (q.bits, q.abits) for p, q in iter_qtensors(qtree)}
        got = {p: (q.bits, q.abits) for p, q in iter_qtensors(back)}
        assert orig == got and any(ab == 4 for _, ab in got.values())
        for a, b in zip(jax.tree_util.tree_leaves(qtree),
                        jax.tree_util.tree_leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

def test_engine_activation_bit_policy():
    from repro.serving.engine import Engine, EngineConfig
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    eng = Engine(params, cfg, EngineConfig(
        batch_size=2, cache_len=32, quantize=True, ql=8, group_size=32,
        quant_kv=False, plan="rules:mlp=4a6,default=6a8"))
    abits = {p: q.abits for p, q in iter_qtensors(eng.params)}
    assert abits["['blocks']['mlp']['w_up']"] == 6
    assert abits["['blocks']['attn']['wq']"] == 8
    assert eng.stats()["mixed_precision"]
    eng.submit([1, 2, 3], max_new_tokens=4)
    done = eng.run()
    assert len(done) == 1 and len(done[0].tokens) == 4
