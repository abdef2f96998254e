"""Self-speculative decoding: the draft= plan axis (grammar, JSON,
validation), round pricing, the accept/rollback invariants that keep
greedy output token-identical across ring and paged pools, the planner's
draft="auto" grid solve, the controller's round-aware SLO budget, and
the engine's gating/capacity guards."""
import dataclasses

import jax
import numpy as np
import pytest

import repro.configs as C
from repro import planning
from repro.core import cost_model as cm
from repro.models import lm
from repro.models.sail_linear import QuantPolicy
from repro.planning import (DecodeCostModel, DraftSpec, PlanSpec, Planner,
                            Slo, expected_tokens_per_round, policy_units,
                            speculative_round_seconds)
from repro.serving.control import ControllerConfig, SloController
from repro.serving.engine import Engine, EngineConfig
from repro.serving.speculative import (SpeculativeDecoder, draft_policy,
                                       measure_acceptance)


@pytest.fixture(scope="module")
def tiny():
    cfg = C.get_smoke("tinymistral_248m")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


PROMPTS = [[1, 2, 3], [4, 5, 6, 7], [8, 9], [3, 1, 4, 1, 5]]

# min_size=1024 so the smoke model's tensors actually quantize — at the
# planner default (65536) every smoke tensor stays f32 and the draft
# tree would be bit-identical to the conservative one, voiding the test
BASE = QuantPolicy(bits=8, group_size=32, min_size=1024, act_bits=8)


def make_engine(tiny, plan, batch=4, **kw):
    cfg, params = tiny
    return Engine(params, cfg, EngineConfig(
        batch_size=batch, cache_len=64, quantize=True, group_size=32,
        min_size=1024, quant_kv=False, plan=plan, **kw))


def run_all(eng, max_new=8, prompts=PROMPTS):
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new)
    return {c.uid: c.tokens for c in eng.run()}


# --- DraftSpec: grammar, JSON, validation -----------------------------------


def test_draft_grammar_round_trip():
    p = PlanSpec.parse("uniform:8a8,draft=q4a8:k3")
    assert p.draft == DraftSpec(weight_bits=4, act_bits=8, k=3)
    assert p.solved
    assert PlanSpec.parse(p.format()) == p
    assert PlanSpec.from_json(p.to_json()) == p
    # weight-only draft token
    q = PlanSpec.parse("uniform:8,draft=q2:k4")
    assert q.draft == DraftSpec(weight_bits=2, act_bits=None, k=4)
    assert PlanSpec.parse(q.format()) == q


def test_draft_auto_keeps_plan_unsolved():
    p = PlanSpec.parse("uniform:8a8,draft=auto")
    assert p.draft == "auto"
    assert not p.solved
    assert PlanSpec.parse(p.format()) == p
    assert PlanSpec.from_json(p.to_json()) == p


def test_draft_json_carries_acceptance_grammar_drops_it():
    """The measured acceptance is probe provenance: durable in the JSON
    artifact, absent from the compact grammar form."""
    d = DraftSpec(weight_bits=4, act_bits=8, k=3, acceptance=0.83)
    assert DraftSpec.from_json(d.to_json()) == d
    assert d.format() == "q4a8:k3"
    assert DraftSpec.parse(d.format()).acceptance is None


def test_draftless_plan_hash_unchanged():
    """Adding the draft axis must not move pre-draft plan hashes: the
    key is omitted when unset."""
    p = PlanSpec.parse("uniform:8a8")
    assert "draft" not in p.to_json()
    assert p.spec_hash == dataclasses.replace(p, draft=None).spec_hash


@pytest.mark.parametrize("bad", [
    dict(weight_bits=7),
    dict(weight_bits=4, k=0),
    dict(weight_bits=4, acceptance=1.5),
    dict(weight_bits=4, act_bits=3),
])
def test_draft_validation_rejects(bad):
    with pytest.raises(ValueError):
        DraftSpec(**bad)


def test_draft_grammar_rejects_malformed():
    with pytest.raises(ValueError):
        DraftSpec.parse("q4k3")
    with pytest.raises(ValueError):
        DraftSpec.parse("qa8:k3")      # must pin weight bits


# --- round pricing ----------------------------------------------------------


def test_expected_tokens_per_round_bounds():
    for k in (1, 3, 8):
        assert expected_tokens_per_round(0.0, k) == pytest.approx(1.0)
        assert expected_tokens_per_round(1.0, k) == pytest.approx(k + 1)
    # monotone in acceptance, bounded by (1, k+1]
    k = 4
    vals = [expected_tokens_per_round(a, k) for a in (0.1, 0.4, 0.7, 0.95)]
    assert vals == sorted(vals)
    assert all(1.0 < v <= k + 1 for v in vals)


def test_speculative_round_seconds_structure(tiny):
    """A round is k draft steps plus ONE verify priced at batch*(k+1)
    rows — so round seconds grow with k, and on a DRAM-bound point the
    verify's byte stream is NOT multiplied by k+1 (weights stream once)."""
    cfg, params = tiny
    policy = BASE
    units = policy_units(params, policy)
    d_units = policy_units(
        params, draft_policy(policy, DraftSpec(weight_bits=2, act_bits=8)))
    cost = DecodeCostModel(batch=4)
    secs = [speculative_round_seconds(cost, units, d_units,
                                      policy.group_size, 0, k)
            for k in (1, 2, 4)]
    assert secs == sorted(secs) and secs[0] > 0
    # DRAM-bound machine: one round's bytes ~ k drafts + one conservative
    # stream, strictly less than k+1 conservative streams
    slow = DecodeCostModel(machine=cm.SailMachine(dram_bw=2.0e9), batch=4)
    k = 4
    round_s = speculative_round_seconds(slow, units, d_units,
                                        policy.group_size, 0, k)
    per_tok = slow.iteration_seconds(slow.cycles(units),
                                     slow.qbytes(units, policy.group_size))
    assert round_s < (k + 1) * per_tok


# --- acceptance rule (pure, no engine) --------------------------------------


def test_greedy_accept_is_exact_argmax_prefix():
    dec = object.__new__(SpeculativeDecoder)      # accept() needs no state
    v = np.zeros((2, 4, 8), np.float32)           # B=2, k=3, V=8
    # lane 0: verifier argmaxes 5,6,7 then bonus 1 — draft matches all
    for j, t in enumerate((5, 6, 7, 1)):
        v[0, j, t] = 9.0
    # lane 1: verifier argmaxes 2,3,4 then 1 — draft diverges at step 1
    for j, t in enumerate((2, 3, 4, 1)):
        v[1, j, t] = 9.0
    draft = np.array([[5, 6, 7], [2, 9, 4]])
    n_acc, nxt = SpeculativeDecoder.accept(dec, draft, v, None)
    assert n_acc.tolist() == [3, 1]
    # lane 0 gets the bonus token, lane 1 the correction at the rejection
    assert nxt.tolist() == [1, 3]


def test_stochastic_accept_full_acceptance_when_q_equals_p():
    """With draft == target distribution the p/q ratio is 1: every draft
    accepted, bonus drawn from row k."""
    dec = object.__new__(SpeculativeDecoder)
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(1, 4, 8)).astype(np.float32)
    draft = np.array([[int(np.argmax(logits[0, j])) for j in range(3)]])
    n_acc, nxt = SpeculativeDecoder.accept(
        dec, draft, logits, logits.copy(), temperature=0.7, seed=0,
        uids=np.array([5]), indices=np.array([0]))
    assert n_acc.tolist() == [3]
    assert 0 <= int(nxt[0]) < 8


# --- measured acceptance ----------------------------------------------------


def test_measure_acceptance_same_bits_is_one(tiny):
    """Identical draft and conservative quantization agree everywhere:
    the acceptance probe must read exactly 1.0 (it is the same tree)."""
    cfg, params = tiny
    a = measure_acceptance(params, cfg, BASE, draft_bits=8, act_bits=8,
                           prompt=[1, 2, 3, 5], n_tokens=8)
    assert a == 1.0


def test_measure_acceptance_lossy_in_unit_interval(tiny):
    cfg, params = tiny
    a = measure_acceptance(params, cfg, BASE, draft_bits=2, act_bits=8,
                           prompt=[1, 2, 3, 5], n_tokens=8)
    assert 0.0 <= a <= 1.0


# --- engine rounds: token identity, rollback, stats -------------------------


def test_lossy_draft_token_identical_with_rollbacks(tiny):
    """The q4 draft disagrees with the q8 verifier (acceptance < 1), so
    rounds roll back — and greedy output must STILL be token-identical
    to per-token decode under the conservative plan alone."""
    base = run_all(make_engine(tiny, "uniform:8a8"))
    eng = make_engine(tiny, "uniform:8a8,draft=q4a8:k3")
    out = run_all(eng)
    assert out == base
    st = eng.stats()["speculative"]
    assert st["rounds"] > 0
    # k drafts per ACTIVE lane per round (lanes retire as budgets finish)
    assert 0 < st["drafted"] <= st["rounds"] * eng.ecfg.batch_size * 3
    assert st["drafted"] % 3 == 0
    assert 0.0 < st["acceptance_rate"] < 1.0     # rejections happened
    # rounds commit multiple tokens: fewer iterations than tokens
    assert eng.iterations < sum(len(t) for t in out.values())


def test_same_precision_draft_accepts_everything(tiny):
    """draft bits == plan bits -> the two trees are identical, verify
    argmax == draft argmax at every position: rule-level acceptance is
    exactly 1.0 even though max_new truncates some commits."""
    base = run_all(make_engine(tiny, "uniform:8a8"))
    eng = make_engine(tiny, "uniform:8a8,draft=q8a8:k3")
    assert run_all(eng) == base
    st = eng.stats()["speculative"]
    assert st["rounds"] > 0
    assert st["acceptance_rate"] == 1.0


def test_paged_pool_round_trip_and_invariants(tiny):
    """Speculative rounds over the paged pool: rollback truncates block
    tails, output stays token-identical, and the pool drains clean."""
    base = run_all(make_engine(tiny, "uniform:8a8"))
    eng = make_engine(tiny, "uniform:8a8,draft=q4a8:k3", kv_block_size=8)
    assert run_all(eng) == base
    eng.block_mgr.check_invariants()
    bp = eng.stats()["block_pool"]
    assert bp["used_blocks"] == 0                # every table freed
    assert eng.stats()["speculative"]["rounds"] > 0


def test_stochastic_rounds_complete_and_rollback(tiny):
    """temperature > 0 exercises the p/q coin-flip path: every request
    must still complete its budget with legal tokens."""
    cfg, _ = tiny
    eng = make_engine(tiny, "uniform:8a8,draft=q4a8:k3",
                      temperature=0.8, seed=11)
    out = run_all(eng, max_new=6)
    assert all(len(t) == 6 for t in out.values())
    assert all(0 <= tok < cfg.vocab for t in out.values() for tok in t)
    assert eng.stats()["speculative"]["rounds"] > 0


# --- sampling determinism (slot vs paged, temperature > 0) ------------------


def test_sampled_tokens_invariant_to_pool_layout(tiny):
    """The (seed, uid, position)-keyed sampler must emit identical
    sequences whether KV lives in the slot pool or the paged pool — the
    pool layout changes WHERE state lives, never the key stream."""
    slot = run_all(make_engine(tiny, "uniform:8a8",
                               temperature=0.7, seed=7))
    paged = run_all(make_engine(tiny, "uniform:8a8",
                                temperature=0.7, seed=7, kv_block_size=8))
    assert slot == paged
    # and the draw is genuinely stochastic: greedy differs somewhere
    greedy = run_all(make_engine(tiny, "uniform:8a8"))
    assert slot != greedy


# --- planner: draft="auto" --------------------------------------------------


def _seeded_planner(tiny, cost=None):
    cfg, params = tiny
    pl = Planner(params, cfg, "uniform:8a8,draft=auto", base=BASE, cost=cost)
    # pre-seed the measured-acceptance cache so the grid solve runs
    # without the (slow) teacher-forced probes
    for bits, acc in ((2, 0.35), (3, 0.6), (4, 0.9)):
        pl._draft_acceptance[(bits, 8)] = acc
    return pl


def test_draft_auto_compute_bound_resolves_to_none(tiny):
    """On the compute-bound default machine verify cycles scale with
    k+1 rows — speculation cannot win, and the honest solve strips the
    draft rather than pinning a losing one."""
    res = _seeded_planner(tiny).solve()
    assert res.spec.draft is None
    assert res.spec.solved


def test_draft_auto_dram_bound_picks_measured_draft(tiny):
    """On a DRAM-bound machine the draft's byte gap pays: the grid solve
    must pin a concrete DraftSpec carrying the measured acceptance."""
    cost = DecodeCostModel(machine=cm.SailMachine(dram_bw=2.0e9), batch=1)
    pl = _seeded_planner(tiny, cost=cost)
    res = pl.solve()
    d = res.spec.draft
    assert isinstance(d, DraftSpec)
    assert res.spec.solved
    assert d.acceptance == pl._draft_acceptance[(d.weight_bits, 8)]
    # deterministic: re-solving from the same cache picks the same draft
    assert _seeded_planner(tiny, cost=cost).solve().spec.draft == d
    # the solved spec round-trips with its provenance
    assert PlanSpec.from_json(res.spec.to_json()).draft == d


# --- controller: rounds, not tokens -----------------------------------------


def test_controller_budget_scales_with_expected_tokens():
    """One speculative round commits E[accepted+1] tokens per lane, so
    the SLO's per-iteration latency budget scales by tokens_per_iter —
    an occupancy infeasible per-token can be feasible per-round."""
    iter_seconds = lambda b: 0.002 * b
    slo = Slo(1000.0, batch=8)            # 8 ms per plain iteration
    per_token = SloController(ControllerConfig(), slo=slo,
                              iter_seconds=iter_seconds)
    assert per_token.meets_slo_at(4) and not per_token.meets_slo_at(5)
    rounds = SloController(ControllerConfig(), slo=slo,
                           iter_seconds=iter_seconds, tokens_per_iter=3.0)
    assert rounds.meets_slo_at(8)
    assert rounds.batch_cap(8) == 8
    # plan_changed with a new expected-tokens updates the budget in place
    rounds.plan_changed(iter_seconds=iter_seconds, tokens_per_iter=1.0)
    assert not rounds.meets_slo_at(8)


# --- gating and capacity guards ---------------------------------------------


def test_draft_rejects_tap_and_recurrent_family(tiny):
    with pytest.raises(ValueError, match="ActivationTap"):
        make_engine(tiny, "uniform:8a8,draft=q4:k2", tap_capacity=16)
    scfg = C.get_smoke("xlstm_350m")
    sparams = lm.init_params(jax.random.PRNGKey(0), scfg)
    with pytest.raises(ValueError, match="attention"):
        Engine(sparams, scfg, EngineConfig(
            batch_size=2, cache_len=32, quantize=True, min_size=1024,
            plan="uniform:8a8,draft=q4:k2"))


def test_submit_reserves_draft_lookahead(tiny):
    """The ring must never wrap across a rollback: a request whose
    prompt + budget + k + 1 exceeds the ring is rejected up front."""
    eng = make_engine(tiny, "uniform:8a8,draft=q4a8:k3", batch=2)
    with pytest.raises(ValueError, match="ring holds"):
        eng.submit([1] * 10, max_new_tokens=60)
    # the same request fits a draft-less engine (no lookahead reserve)
    plain = make_engine(tiny, "uniform:8a8", batch=2)
    assert plain.submit([1] * 10, max_new_tokens=54) > 0
