"""Planning API: PlanSpec round-trips and input forms, SLO->budget
derivation, the DRAM-aware objective, Planner solve/replan, the
ActivationTap capture path, live plan swaps in the engine, checkpoint
plan provenance, and the joint-solver Pareto pruning regression."""
import dataclasses
import json
import tempfile

import jax
import numpy as np
import pytest

from repro.core import cost_model as cm
from repro.core import sensitivity as sens
from repro.models import lm
from repro.models.common import ModelConfig
from repro.models.sail_linear import QuantPolicy, quantize_params
from repro.planning import (ActivationTap, DecodeCostModel, Planner,
                            PlanRule, PlanSpec, Slo, as_plan, resolve_plan)


def tiny_cfg(**kw):
    base = dict(name="tiny", family="dense", vocab=64, d_model=32,
                n_layers=2, n_heads=4, n_kv=2, d_ff=64, act="swiglu",
                attn_chunk=16, max_seq=128)
    base.update(kw)
    return ModelConfig(**base)


BASE = dict(group_size=32, min_size=1024)


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_cfg()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def probes(tiny):
    """One set of sensitivity probes shared by every solver test."""
    cfg, params = tiny
    base = QuantPolicy(bits=4, **BASE)
    toks = sens.calibration_tokens(cfg.vocab, 2, 16)
    scores = sens.output_sensitivity(params, cfg, toks, base)
    act_scores = sens.activation_sensitivity(params, cfg, toks, base)
    return base, toks, scores, act_scores


# ---------------------------------------------------------------------------
# PlanSpec: JSON <-> grammar round-trips, input forms
# ---------------------------------------------------------------------------

DOCUMENTED_SPECS = [
    "uniform:4",
    "uniform:4a8",
    "uniform:6",
    "rules:mlp=3,attn=5,default=4",
    "rules:mlp=4a6,attn=5a8,default=6a8",
    "rules:attn=5a6,mlp=3",
    "auto:q4",
    "auto:4.5bpw",
    "auto:q4a8",
    "auto:q4a8,prt=measured,maxseg=4",
    "auto:q4a8,prt=measured,slo=120",
]


@pytest.mark.parametrize("spec", DOCUMENTED_SPECS)
def test_planspec_grammar_and_json_roundtrip(spec):
    plan = PlanSpec.parse(spec)
    # grammar round-trip at the spec level (format() is canonical)
    assert PlanSpec.parse(plan.format()) == plan
    # JSON round-trip is exact
    assert PlanSpec.from_json(plan.to_json()) == plan
    # and file round-trip
    with tempfile.TemporaryDirectory() as d:
        plan.save(d + "/plan.json")
        assert PlanSpec.load(d + "/plan.json") == plan


def test_planspec_solved_json_roundtrip():
    plan = PlanSpec.parse("auto:q4a8,prt=measured").with_solution(
        {"['blocks']['mlp']['w_up']": (4, 4, 6, 6), "['lm_head']": 5},
        {"['blocks']['mlp']['w_up']": (8, 8, 6, 6), "['lm_head']": 8})
    assert plan.solved
    back = PlanSpec.from_json(plan.to_json())
    assert back == plan
    assert back.spec_hash == plan.spec_hash
    # the solved allocation has no grammar form, but the request does
    assert PlanSpec.parse(plan.format()).mode == "auto"


def test_planspec_validation():
    with pytest.raises(ValueError):
        PlanSpec(mode="nope")
    with pytest.raises(ValueError):
        PlanSpec(weight_bits=7)
    with pytest.raises(ValueError):
        PlanSpec(act_bits=5)
    with pytest.raises(ValueError):
        PlanSpec(prt="sometimes")
    with pytest.raises(ValueError):
        PlanSpec(max_segments=0)
    with pytest.raises(ValueError):
        PlanSpec(mode="uniform", weight_bits=None)
    with pytest.raises(ValueError):
        PlanSpec.parse("auto:q4a8,prt=sometimes")
    with pytest.raises(ValueError):
        PlanSpec.parse("uniform:4b8")


@pytest.mark.parametrize("spec", DOCUMENTED_SPECS)
def test_parse_bit_policy_shim_equivalence(spec):
    """Every form ``EngineConfig.plan`` accepts — the grammar string, the
    PlanSpec, its JSON dict and that dict through JSON text — names one
    plan, with one hash, whose canonical grammar parses back to it."""
    plan = PlanSpec.parse(spec)
    forms = (spec, plan, plan.to_json(), json.loads(json.dumps(plan.to_json())))
    for form in forms:
        assert as_plan(form) == plan
        assert as_plan(form).spec_hash == plan.spec_hash
    assert PlanSpec.parse(plan.format()).format() == plan.format()


def test_resolve_bit_policy_shim(tiny):
    cfg, params = tiny
    base = QuantPolicy(bits=4, **BASE)
    pol = resolve_plan("uniform:6a8", params, cfg, base=base).policy
    assert pol.bits == 6 and pol.act_bits == 8
    pol = resolve_plan("rules:mlp=4a6,default=6a8", params, cfg,
                       base=base).policy
    assert pol.act_rules == (("mlp", 6),) and pol.act_bits == 8
    # resolving needs no solve for these: it is the spec's own policy
    assert pol == PlanSpec.parse("rules:mlp=4a6,default=6a8").to_policy(base)


def test_planspec_policy_bridge_roundtrip():
    base = QuantPolicy(bits=4, **BASE)
    for spec in ("uniform:6a8", "rules:mlp=2a4,default=6"):
        pol = PlanSpec.parse(spec).to_policy(base)
        again = PlanSpec.from_policy(pol).to_policy(base)
        assert again == pol


def test_legacy_act_only_rules_preserved():
    """Weight rules and activation rules apply independently: an
    activation-only rule (no weight bits) must reach the policy instead
    of being silently dropped, and survive the JSON round trip."""
    base = QuantPolicy(bits=4, **BASE)
    plan = PlanSpec(mode="rules", rules=(PlanRule("attn", 4),
                                         PlanRule("mlp", None, 6)))
    pol = plan.to_policy(base)
    assert pol.rules == (("attn", 4),)
    assert pol.act_rules == (("mlp", 6),)
    assert PlanSpec.from_json(plan.to_json()) == plan
    # act-only rule tokens have a grammar form too
    assert PlanSpec.parse(plan.format()) == plan
    assert PlanSpec.parse("rules:mlp=a6,default=4").rules == (
        planning_rule("mlp", None, 6),)
    with pytest.raises(ValueError):
        PlanSpec.parse("rules:mlp=,default=4")


def planning_rule(pattern, wb, ab):
    from repro.planning import PlanRule
    return PlanRule(pattern, wb, ab)


def test_plan_cost_prices_cycles_at_the_quoted_batch(tiny):
    """evaluate(batch=) must reprice the whole iteration at that batch —
    lookup cycles scale with it — never divide batch-32 tokens by a
    batch-8 iteration time."""
    cfg, params = tiny
    cost = DecodeCostModel(batch=8)
    pol = QuantPolicy(bits=4, act_bits=8, **BASE)
    c8 = cost.evaluate(params, pol)
    c32 = cost.evaluate(params, pol, batch=32)
    assert c32.cycles > c8.cycles
    assert c32 == DecodeCostModel(batch=32).evaluate(params, pol)


# ---------------------------------------------------------------------------
# SLO -> budgets and the DRAM-aware objective
# ---------------------------------------------------------------------------

def test_slo_budget_derivation_monotone():
    cost = DecodeCostModel()
    targets = [10.0, 100.0, 1000.0, 10000.0]
    budgets = [cost.budgets(Slo(t, batch=8), fixed_bytes=4096)
               for t in targets]
    for lo, hi in zip(budgets, budgets[1:]):
        # a higher tokens/s target can only shrink both budgets
        assert hi.cycle_budget < lo.cycle_budget
        assert hi.byte_budget < lo.byte_budget
        assert hi.seconds < lo.seconds
    # exact decomposition: meeting both budgets implies meeting the SLO
    b = budgets[1]
    tps = cost.tokens_per_second(b.cycle_budget, b.byte_budget + 4096,
                                 batch=8)
    assert tps >= 100.0 * (1 - 1e-9)
    # the SLO is infeasible when fixed bytes alone exceed the stream
    with pytest.raises(ValueError):
        cost.budgets(Slo(1e18, batch=1), fixed_bytes=1 << 40)


def test_dram_term_penalizes_byte_heavy_plans(tiny):
    """The DRAM-aware objective: at equal-ish cycles, a byte-heavy plan
    loses once t_dram dominates — and the legacy compute-only model
    cannot see the difference."""
    cfg, params = tiny
    machine = dataclasses.replace(cm.SailMachine(), dram_bw=1e9)
    dram = DecodeCostModel(machine=machine)
    legacy = DecodeCostModel(machine=machine, include_dram=False)
    q4 = QuantPolicy(bits=4, act_bits=8, **BASE)
    q8 = QuantPolicy(bits=8, act_bits=8, **BASE)
    c4, c8 = dram.evaluate(params, q4), dram.evaluate(params, q8)
    assert c8.quant_bytes > c4.quant_bytes
    assert c8.dram_bound
    assert c8.tokens_per_second < c4.tokens_per_second
    # compute-only pricing: 8-bit lookups cost MORE cycles, but the gap
    # is the compute ratio, not the byte ratio — the byte-heavy penalty
    # under DRAM must exceed what cycles alone explain
    l4, l8 = legacy.evaluate(params, q4), legacy.evaluate(params, q8)
    assert l4.t_dram == 0.0 and l4.fixed_bytes == 0
    # once DRAM-bound, throughput tracks the byte footprint exactly —
    # the term the compute-only model was blind to
    assert c4.dram_bound and c8.dram_bound
    dram_ratio = c4.tokens_per_second / c8.tokens_per_second
    byte_ratio = c8.total_bytes / c4.total_bytes
    assert dram_ratio == pytest.approx(byte_ratio, rel=1e-9)
    assert legacy.evaluate(params, q8).t_dram == 0.0


def test_slo_solve_meets_target_and_dominates_fixed_budget(tiny, probes):
    """The bench's --slo --check claim, asserted at test scale: the
    SLO-derived plan meets its target under the DRAM-aware model and
    reaches lower predicted error than the byte-blind fixed-cycle-budget
    solve at equal modeled throughput."""
    cfg, params = tiny
    base, toks, scores, act_scores = probes
    machine = dataclasses.replace(cm.SailMachine(), dram_bw=2e9)
    cost = DecodeCostModel(machine=machine, prt="paper")
    bpol, brep = sens.calibrate_policy(
        params, cfg, base, match_uniform=4, match_uniform_abits=8,
        abits_candidates=sens.SUPPORTED_ABITS, scores=scores,
        act_scores=act_scores, machine=machine)
    bcost = cost.evaluate(params, bpol)
    planner = Planner(params, cfg, PlanSpec.parse("auto:q4a8"), base=base,
                      cost=cost, tokens=toks, scores=scores,
                      act_scores=act_scores)
    res = planner.solve(slo=Slo(bcost.tokens_per_second, batch=8))
    assert res.meets_slo
    assert res.cost.tokens_per_second >= bcost.tokens_per_second * (1 - 1e-9)
    assert res.report.predicted_error <= brep.predicted_error + 1e-12
    # the solved spec is self-contained: rebuilding the policy from its
    # JSON serves the identical tree
    back = PlanSpec.from_json(res.spec.to_json()).to_policy(base)
    assert back.allocation == res.policy.allocation


def test_slo_solve_error_monotone_in_target(tiny, probes):
    cfg, params = tiny
    base, toks, scores, act_scores = probes
    machine = dataclasses.replace(cm.SailMachine(), dram_bw=2e9)
    planner = Planner(params, cfg, PlanSpec.parse("auto:q4a8"), base=base,
                      cost=DecodeCostModel(machine=machine),
                      tokens=toks, scores=scores, act_scores=act_scores)
    ref = DecodeCostModel(machine=machine).evaluate(
        params, dataclasses.replace(base, act_bits=8))
    errs = []
    for frac in (0.5, 0.75, 1.0):
        res = planner.solve(slo=Slo(ref.tokens_per_second * frac, batch=8))
        errs.append(res.report.predicted_error)
    # tighter SLO (higher target) -> shrinking budgets -> error rises
    assert errs[0] <= errs[1] + 1e-12 <= errs[2] + 2e-12


# ---------------------------------------------------------------------------
# joint-solver Pareto pruning (ROADMAP scaling item)
# ---------------------------------------------------------------------------

def saturating_units(n_layers=32, paths=("a", "b", "c", "d", "e", "f"),
                     k=64, n=64, seed=0):
    """Synthetic calibration-shaped units at 32-layer/200-unit scale with
    realistic saturating error ladders (several wide precisions reach the
    same floor — exactly where dominated states appear)."""
    rng = np.random.default_rng(seed)
    units = []
    for p in paths:
        for layer in range(n_layers):
            sc = float(rng.uniform(0.5, 2.0))
            asc = float(rng.uniform(0.1, 0.5))
            errors = {b: sc * max(2.0 ** -b, 2.0 ** -5) for b in (2, 3, 4, 5, 6, 8)}
            aerrors = {ab: asc * max(2.0 ** -ab, 2.0 ** -6) for ab in (4, 6, 8)}
            units.append(sens.Unit(path=f"['{p}']", layer=layer, k=k, n=n,
                                   copies=1, errors=errors,
                                   aerrors=aerrors))
    return units


def test_pareto_pruning_identical_allocations_and_bounded_candidates():
    units = saturating_units()
    assert len(units) == 192    # ~200-unit/32-layer scale
    full = [(wb, ab) for wb in (2, 3, 4, 5, 6, 8) for ab in (4, 6, 8)]
    # bounded candidate count: saturation makes {6,8}-bit weight states
    # and 8-bit act states dominated wherever the floor is reached
    total = 0
    for u in units[:24]:
        kept = sens.pareto_state_filter(
            full, lambda s: u.errors[s[0]] + u.aerrors[s[1]],
            lambda s: s[0] * s[1])   # any cost monotone in both bits
        assert len(kept) < len(full)
        total += len(kept)
    assert total <= 24 * 10    # vs 24 * 18 unpruned
    # identical allocations with pruning on and off, across budgets
    ref_cycles = sens.allocate_bits_joint(units, 1e12, 32).cycles_total
    for frac in (0.5, 0.8):
        a = sens.allocate_bits_joint(units, ref_cycles * frac, 32,
                                     prune_states=True)
        b = sens.allocate_bits_joint(units, ref_cycles * frac, 32,
                                     prune_states=False)
        assert a.bits_by_unit == b.bits_by_unit
        assert a.predicted_error == b.predicted_error


def test_pareto_pruning_identity_on_real_probes(tiny, probes):
    """The smoke-config regression: pruned and unpruned solves agree on
    real sensitivity scores (pruning only removes states that cannot
    appear in any improving move)."""
    cfg, params = tiny
    base, toks, scores, act_scores = probes
    units = []
    flat = {jax.tree_util.keystr(p): w
            for p, w in jax.tree_util.tree_flatten_with_path(params)[0]}
    for key, errs in scores.items():
        path, layer = key
        w = flat[path]
        copies = int(w.shape[0]) if (layer is None and w.ndim > 2) else 1
        units.append(sens.Unit(path=path, layer=layer,
                               k=int(w.shape[-2]), n=int(w.shape[-1]),
                               copies=copies, errors=errs,
                               aerrors=act_scores[key]))
    ref = sens.allocate_bits_joint(units, 1e12, 32).cycles_total
    a = sens.allocate_bits_joint(units, ref * 0.6, 32, prune_states=True)
    b = sens.allocate_bits_joint(units, ref * 0.6, 32, prune_states=False)
    assert a.bits_by_unit == b.bits_by_unit


# ---------------------------------------------------------------------------
# ActivationTap + engine integration
# ---------------------------------------------------------------------------

def test_tap_capture_shapes_and_capacity():
    tap = ActivationTap(capacity=8)
    xs = np.arange(2 * 3 * 1 * 4, dtype=np.float32).reshape(2, 3, 1, 4)
    mask = np.array([True, False, True])
    tap.observe(xs, mask)
    assert tap.n_layers == 2 and len(tap) == 2    # masked lane dropped
    for _ in range(10):
        tap.observe(xs, mask)
    assert len(tap) == 8                          # ring capacity
    calib = tap.calib()
    assert set(calib) == {0, 1, None}
    assert calib[0].shape == (8, 4) and calib[None].ndim == 2
    tap.clear()
    assert tap.calib() is None


def test_engine_tap_captures_decode_inputs(tiny):
    cfg, params = tiny
    from repro.serving.engine import Engine, EngineConfig
    eng = Engine(params, cfg, EngineConfig(
        batch_size=2, cache_len=32, quantize=True, ql=4, group_size=32,
        plan="uniform:4a8", tap_capacity=64))
    eng.submit([1, 2, 3], max_new_tokens=6)
    eng.submit([4, 5], max_new_tokens=6)
    eng.run()
    assert eng.tap.n_layers == cfg.n_layers
    calib = eng.tap.calib()
    assert calib[0].shape[1] == cfg.d_model
    assert eng.stats()["tapped_rows"] == eng.tap.rows_seen > 0


def test_engine_token_identity_across_live_replan_swap(tiny):
    """Requantizing mid-serve under the same plan must not disturb a
    single token: the KV pool and scheduler state survive the swap."""
    cfg, params = tiny
    from repro.serving.engine import Engine, EngineConfig

    def run(swap_iterations=()):
        eng = Engine(params, cfg, EngineConfig(
            batch_size=2, cache_len=32, quantize=True, ql=4,
            group_size=32, plan="uniform:4a8", tap_capacity=32))
        eng.submit([1, 2, 3], max_new_tokens=8)
        eng.submit([4, 5, 6, 7], max_new_tokens=8)
        while True:
            more = eng.step()
            if eng.iterations in swap_iterations:
                # force the full requantize-and-swap path (same-policy
                # swaps are otherwise skipped as no-ops)
                eng.apply_plan(eng.plan, force_requantize=True)
            if not more:
                break
        return {c.uid: c.tokens for c in eng.completions.values()}, eng

    ref, _ = run()
    swapped, eng = run(swap_iterations=(3, 5))
    assert swapped == ref
    assert eng.replan_count == 2
    assert eng.stats()["replan_count"] == 2


def test_engine_replan_measures_prt_from_traffic(tiny):
    cfg, params = tiny
    from repro.serving.engine import Engine, EngineConfig
    eng = Engine(params, cfg, EngineConfig(
        batch_size=2, cache_len=32, quantize=True, ql=4, group_size=32,
        plan="uniform:4a8", tap_capacity=64))
    eng.submit([1, 2, 3], max_new_tokens=8)
    eng.run()
    res = eng.replan()
    assert 0.0 <= res.measured_prt_hit_rate <= 1.0
    st = eng.stats()
    assert st["replan_count"] == 1
    assert st["prt_hit_rate"] == res.measured_prt_hit_rate
    assert eng.plan.prt == "measured"
    # the swap kept the uniform serving plan's allocation semantics
    assert eng.quant_policy.bits == 4


def test_engine_plan_equals_legacy_bit_policy(tiny):
    """A grammar string, its PlanSpec and the PlanSpec's JSON dict serve
    one quant_policy; a plan without quantization is refused."""
    cfg, params = tiny
    from repro.serving.engine import Engine, EngineConfig
    spec = PlanSpec.parse("rules:mlp=2,default=6")
    pols = [Engine(params, cfg, EngineConfig(
        batch_size=2, cache_len=32, quantize=True, ql=4, group_size=32,
        plan=form)).quant_policy
        for form in ("rules:mlp=2,default=6", spec, spec.to_json())]
    assert pols[0] == pols[1] == pols[2]
    assert pols[0].rules == (("mlp", 2),) and pols[0].bits == 6
    with pytest.raises(ValueError):
        Engine(params, cfg, EngineConfig(quantize=False, plan="uniform:4"))


def test_engine_serves_solved_plan_without_recalibration(tiny, probes):
    """A solved auto plan (plan.json contents) must rebuild the exact
    policy with no Planner run — the deploy-time path."""
    cfg, params = tiny
    base, toks, scores, act_scores = probes
    from repro.serving.engine import Engine, EngineConfig
    planner = Planner(params, cfg, PlanSpec.parse("auto:q4a8"), base=base,
                      tokens=toks, scores=scores, act_scores=act_scores)
    res = planner.solve()
    eng = Engine(params, cfg, EngineConfig(
        batch_size=2, cache_len=32, quantize=True, ql=4, group_size=32,
        plan=res.spec.to_json()))
    assert eng.quant_policy.allocation == res.policy.allocation
    eng.submit([1, 2, 3], max_new_tokens=3)
    assert len(eng.run()) == 1


def test_engine_warns_on_unreachable_slo(tiny):
    """An SLO the served plan cannot meet must never pass silently —
    whether the plan arrived pre-solved or the solve just missed."""
    cfg, params = tiny
    from repro.serving.engine import Engine, EngineConfig
    with pytest.warns(UserWarning, match="below the requested SLO"):
        Engine(params, cfg, EngineConfig(
            batch_size=2, cache_len=32, quantize=True, ql=4,
            group_size=32, plan="uniform:4a8", slo=1e12))


# ---------------------------------------------------------------------------
# checkpoint plan provenance
# ---------------------------------------------------------------------------

def test_checkpoint_carries_plan(tiny, probes):
    cfg, params = tiny
    base, toks, scores, act_scores = probes
    from repro import checkpoint as ckpt
    planner = Planner(params, cfg, PlanSpec.parse("auto:q4a8"), base=base,
                      tokens=toks, scores=scores, act_scores=act_scores)
    res = planner.solve()
    qtree, _, _ = quantize_params(params, res.policy)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save_quantized(d, 0, qtree, res.policy, plan=res.spec)
        restored, extras = ckpt.restore_quantized(d, params)
        plan = ckpt.restored_plan(extras)
        assert plan == res.spec
        assert plan.spec_hash == res.spec.spec_hash
        # plan alone rebuilds the identical policy
        assert plan.to_policy(base).allocation == res.policy.allocation
        # and when no plan is passed, one is derived from the policy,
        # recording the caller's KV flag faithfully
        ckpt.save_quantized(d, 1, qtree, res.policy, quant_kv=False)
        _, extras1 = ckpt.restore_quantized(d, params, step=1)
        derived = ckpt.restored_plan(extras1)
        assert derived is not None and derived.quant_kv is False
        assert derived.to_policy(base).allocation == res.policy.allocation


# ---------------------------------------------------------------------------
# per-layer calibration plumbing
# ---------------------------------------------------------------------------

def test_per_layer_calib_reaches_solver(tiny, probes):
    """A per-layer calib mapping must price units at their own layer's
    hit rate — layers fed pathologically repetitive activations get a
    deeper discount than layers fed noise."""
    rng = np.random.default_rng(0)
    noise = rng.standard_normal((8, 32)).astype(np.float32)
    constant = np.ones((8, 32), np.float32)
    from repro.core.pattern import calib_for_layer, prt_hit_rate
    calib = {0: constant, 1: noise, None: noise}
    assert calib_for_layer(calib, 0) is constant
    assert calib_for_layer(calib, 5) is noise      # fallback
    assert calib_for_layer(noise, 3) is noise      # plain arrays pass
    h_const = prt_hit_rate(4, 8, constant)
    h_noise = prt_hit_rate(4, 8, noise)
    assert h_const > h_noise
    cost = DecodeCostModel(prt="measured", calib=calib)
    c0 = cost.unit_cycles(32, 32, 4, 8, layer=0)
    c1 = cost.unit_cycles(32, 32, 4, 8, layer=1)
    assert c0 < c1
