"""Paged KV block pool: BlockSpaceManager refcount/partition invariants
under random op sequences, copy-on-write prefix sharing, preemption, and
the paged engine's token-identity + equal-memory-concurrency guarantees
against the fixed-slot pool."""
import jax
import numpy as np
import pytest
from _hyp import given, settings, strategies as st

import repro.configs as C
from repro.models import lm
from repro.serving.block_pool import BlockSpaceManager
from repro.serving.engine import Engine, EngineConfig


# --- pure pool properties (no model) ----------------------------------------


def test_allocate_free_round_trip():
    mgr = BlockSpaceManager(num_blocks=8, block_size=4)
    table, shared = mgr.allocate(1, (1, 2, 3, 4, 5))
    assert len(table) == 2 and shared == 0
    assert mgr.used_blocks == 2 and mgr.free_blocks == 6
    mgr.check_invariants()
    mgr.free(1)
    assert mgr.used_blocks == 0 and mgr.free_blocks == 8
    mgr.check_invariants()


def test_duplicate_uid_and_double_free_raise():
    mgr = BlockSpaceManager(num_blocks=4, block_size=4)
    mgr.allocate(1, (1, 2))
    with pytest.raises(KeyError):
        mgr.allocate(1, (1, 2))
    mgr.free(1)
    with pytest.raises(KeyError):
        mgr.free(1)


def test_prefix_sharing_reuses_blocks():
    """Identical prompts share every full block AND the partial frontier;
    the sharer allocates zero fresh blocks."""
    mgr = BlockSpaceManager(num_blocks=8, block_size=4)
    prompt = (1, 2, 3, 4, 5, 6)        # 2 blocks, frontier half-full
    t1, sh1 = mgr.allocate(1, prompt)
    t2, sh2 = mgr.allocate(2, prompt)
    assert sh1 == 0 and sh2 == 2
    assert t1 == t2
    assert mgr.used_blocks == 2        # shared, not duplicated
    mgr.check_invariants()
    mgr.free(1)
    assert mgr.used_blocks == 2        # uid 2 still holds them
    mgr.free(2)
    assert mgr.used_blocks == 0


def test_divergent_prompts_share_only_common_blocks():
    mgr = BlockSpaceManager(num_blocks=16, block_size=4)
    mgr.allocate(1, (1, 2, 3, 4, 9, 9))
    _, sh = mgr.allocate(2, (1, 2, 3, 4, 7, 7))
    assert sh == 1                     # first full block only
    assert mgr.used_blocks == 3
    mgr.check_invariants()


def test_append_inplace_alloc_and_cow():
    """The three append outcomes: within the frontier block (inplace), at
    a block boundary (alloc), and into a SHARED block (copy-on-write)."""
    mgr = BlockSpaceManager(num_blocks=8, block_size=4)
    prompt = (1, 2, 3, 4, 5, 6)
    mgr.allocate(1, prompt)
    mgr.allocate(2, prompt)
    # uid 1 writes position 6: inside the shared frontier block -> COW
    kind, src, dst = mgr.append_slot(1, 6)
    assert kind == "cow" and src != dst
    assert mgr.table(1)[1] == dst and mgr.table(2)[1] == src
    mgr.check_invariants()
    # uid 2 writes position 6: it is now the SOLE owner of src -> inplace
    res = mgr.append_slot(2, 6)
    assert res[0] == "inplace"
    # position 8 crosses a boundary -> fresh block
    kind, _, blk = mgr.append_slot(1, 8)
    assert kind == "alloc" and mgr.table(1)[2] == blk
    mgr.check_invariants()
    mgr.free(1)
    mgr.free(2)
    assert mgr.free_blocks == 8


def test_append_oom_returns_none():
    mgr = BlockSpaceManager(num_blocks=2, block_size=4)
    mgr.allocate(1, (1, 2, 3, 4, 5, 6, 7, 8))
    assert mgr.append_slot(1, 8) is None      # pool dry -> caller preempts
    mgr.preempt(1)
    assert mgr.free_blocks == 2 and mgr.stats()["preemptions"] == 1


def test_truncate_releases_rollback_tail():
    """Speculative rollback: truncate drops every table entry past the
    accepted frontier, returns the drop count, and is refcount-aware —
    a shared block survives until its last owner lets go."""
    mgr = BlockSpaceManager(num_blocks=8, block_size=4)
    mgr.allocate(1, tuple(range(1, 11)))      # 10 tokens -> 3 blocks
    assert mgr.truncate(1, 10) == 0           # frontier kept: no-op
    assert mgr.truncate(1, 5) == 1            # back to 2 blocks
    assert len(mgr.table(1)) == 2 and mgr.used_blocks == 2
    mgr.check_invariants()
    # regrow over the truncated tail: the freed block is reusable
    kind, _, _ = mgr.append_slot(1, 8)
    assert kind == "alloc" and mgr.used_blocks == 3
    mgr.check_invariants()
    # shared tail: the sharer's truncate must NOT free the owner's block
    mgr2 = BlockSpaceManager(num_blocks=8, block_size=4)
    prompt = (1, 2, 3, 4, 5, 6, 7, 8)
    mgr2.allocate(1, prompt)
    mgr2.allocate(2, prompt)                  # shares both blocks
    assert mgr2.truncate(2, 4) == 1
    assert mgr2.used_blocks == 2              # uid 1 still holds block 2
    assert len(mgr2.table(1)) == 2
    mgr2.check_invariants()
    mgr2.free(1)
    mgr2.free(2)
    assert mgr2.used_blocks == 0


def test_truncate_to_zero_frees_everything():
    mgr = BlockSpaceManager(num_blocks=4, block_size=4)
    mgr.allocate(7, (1, 2, 3, 4, 5))
    assert mgr.truncate(7, 0) == 2
    assert mgr.table(7) == [] and mgr.used_blocks == 0
    mgr.check_invariants()


def test_admission_cap_is_a_conservative_lower_bound():
    """admission_cap ignores intra-batch sharing (documented), so it
    lower-bounds actual admissions; once the registrant's blocks exist,
    the estimate prices sharers correctly (zero fresh blocks each)."""
    mgr = BlockSpaceManager(num_blocks=5, block_size=4)
    prompts = [(1, 2, 3, 4, 5)] * 3
    assert mgr.admission_cap(prompts) == 2    # 2 + 2 fresh, third won't fit
    mgr.allocate(0, prompts[0])
    # registry now holds both blocks: every sharer prices at 0 fresh
    assert mgr.admission_cap(prompts[1:]) == 2
    admitted = 1
    for uid, p in enumerate(prompts[1:], start=1):
        assert mgr.can_allocate(p)
        mgr.allocate(uid, p)
        admitted += 1
    assert admitted == 3 and mgr.used_blocks == 2
    mgr.check_invariants()


@settings(max_examples=30)
@given(ops=st.lists(st.tuples(st.integers(0, 3), st.integers(0, 5),
                              st.integers(1, 9)), min_size=1, max_size=60))
def test_invariants_under_random_op_soup(ops):
    """No leak, no double-count, registry in sync: after ANY interleaving
    of allocate/append/preempt/free, the free+used partition and the
    refcount-vs-table-ownership equality hold; freeing every survivor
    returns the pool to fully free."""
    mgr = BlockSpaceManager(num_blocks=12, block_size=4)
    live = {}
    next_uid = 0
    for op, which, plen in ops:
        if op == 0:                                   # allocate
            prompt = tuple(range(1, plen + 1))
            if mgr.can_allocate(prompt):
                mgr.allocate(next_uid, prompt)
                live[next_uid] = plen
                next_uid += 1
        elif op == 1 and live:                        # append one position
            uid = sorted(live)[which % len(live)]
            res = mgr.append_slot(uid, live[uid])
            if res is not None:
                live[uid] += 1
        elif op == 2 and live:                        # preempt
            uid = sorted(live)[which % len(live)]
            mgr.preempt(uid)
            del live[uid]
        elif op == 3 and live:                        # complete
            uid = sorted(live)[which % len(live)]
            mgr.free(uid)
            del live[uid]
        mgr.check_invariants()
    for uid in list(live):
        mgr.free(uid)
    assert mgr.used_blocks == 0
    assert mgr.free_blocks == mgr.num_blocks
    mgr.check_invariants()


# --- engine integration ------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg = C.get_smoke("tinymistral_248m")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_engine(tiny, batch=4, **kw):
    cfg, params = tiny
    opts = dict(batch_size=batch, cache_len=64, quantize=True, ql=4,
                group_size=32, quant_kv=True)
    opts.update(kw)
    return Engine(params, cfg, EngineConfig(**opts))


def _mixed_plan():
    """A solved plan whose per-layer bits split the 2-layer stack into
    two segments (``params["blocks"]`` served as a list)."""
    from repro import planning
    from repro.models.sail_linear import BitAllocation, QuantPolicy
    alloc = BitAllocation(per_path={"['blocks']['mlp']['w_down']": (4, 8)})
    return planning.PlanSpec.from_policy(
        QuantPolicy(bits=4, group_size=32, min_size=1024, allocation=alloc),
        quant_kv=True)


# engine options of each case the paged pool must match the slot pool in
PAGED_CASES = {
    "int8_kv": lambda: {},
    "f32_kv": lambda: {"quant_kv": False},
    "mixed_plan": lambda: {"plan": _mixed_plan()},
    "layer_taps": lambda: {"tap_capacity": 256},
}


PREFIX = [5, 9, 2, 4, 11, 3, 8, 1]
PROMPTS = [PREFIX + [7, 6], PREFIX + [10, 12], PREFIX + [7, 6],
           [1, 2, 3], PREFIX + [13, 14, 15], PREFIX + [7, 6]]


def _serve(eng, prompts, max_new=6):
    uids = [eng.submit(list(p), max_new) for p in prompts]
    eng.run()
    return {u: eng.completions[u].tokens for u in uids}


@pytest.mark.parametrize("case", list(PAGED_CASES))
def test_paged_tokens_identical_to_slot_pool(tiny, case):
    """The tentpole guarantee: gather/scatter through block tables is a
    layout change, not a numerics change — greedy outputs match the
    contiguous slot pool token for token, with int8 or f32 KV, over a
    segmented (mixed-precision) layer stack, and with the activation tap
    seeing the same layer inputs.  Every engine decode step takes its
    pool's buffers over (donation)."""
    slot = make_engine(tiny, **PAGED_CASES[case]())
    paged = make_engine(tiny, kv_block_size=8, **PAGED_CASES[case]())
    ref = _serve(slot, PROMPTS)
    got = _serve(paged, PROMPTS)
    assert got == ref
    if case == "mixed_plan":
        assert isinstance(paged.params["blocks"], list)
        assert len(paged.params["blocks"]) == 2
    if case == "layer_taps":
        assert paged.tap.n_layers == slot.tap.n_layers == 2
        for layer in range(2):
            np.testing.assert_array_equal(paged.tap.rows(layer),
                                          slot.tap.rows(layer))
    for eng in (slot, paged):
        st_ = eng.stats()
        assert st_["decode_iterations"] > 0
        assert st_["decode_pool_donated"] == st_["decode_iterations"]


def test_prefix_sharing_token_identity_and_hits(tiny):
    """Requests sharing a prefix attend to the REGISTRANT'S blocks; that
    must be invisible in the output, and the pool must record the hits."""
    eng = make_engine(tiny, kv_block_size=8)
    got = _serve(eng, PROMPTS)
    ref = _serve(make_engine(tiny, kv_block_size=8, share_prefix=False),
                 PROMPTS)
    assert got == ref
    st_ = eng.block_mgr.stats()
    assert st_["shared_hits"] > 0
    assert st_["used_blocks"] == 0            # everything returned
    eng.block_mgr.check_invariants()


def test_preemption_resumes_with_identical_tokens(tiny):
    """A pool too small for the offered load forces preemption; the
    evicted request re-prefills from its committed tokens and must finish
    with exactly the unpreempted output."""
    ref = _serve(make_engine(tiny), PROMPTS, max_new=8)
    eng = make_engine(tiny, kv_block_size=8, kv_pool_blocks=7)
    got = _serve(eng, PROMPTS, max_new=8)
    assert got == ref
    assert eng.block_mgr.stats()["preemptions"] > 0
    assert any("resumed_iteration" in ev for ev in eng.events.values())


def test_equal_memory_admits_more_with_sharing(tiny):
    """The gate property at test scale: at one fixed KV byte budget, the
    paged pool with prefix sharing holds strictly more requests in
    flight than the slot pool."""
    prompts = [PREFIX + [i, i + 1] for i in range(8)]
    slot = make_engine(tiny, batch=2)          # 2 slots x 64 tokens
    _serve(slot, prompts)
    paged = make_engine(tiny, batch=8, kv_block_size=8,
                        kv_pool_blocks=16)     # same bytes: 16 x 8 tokens
    _serve(paged, prompts)
    assert paged.stats()["peak_active"] > slot.stats()["peak_active"]


def test_paged_rejects_oversized_request(tiny):
    eng = make_engine(tiny, kv_block_size=8)
    with pytest.raises(ValueError, match="never wrap"):
        eng.submit(list(range(60)), 10)        # 70 > 64-token lane


def test_kv_bits_plan_threads_to_engine_and_stats(tiny):
    """PlanSpec.kv_bits overrides EngineConfig.quant_kv and lands in
    stats(); the paged pool prices its budget at that precision."""
    from repro import planning
    cfg, params = tiny
    eng = Engine(params, cfg, EngineConfig(
        batch_size=4, cache_len=64, quantize=True, ql=4, group_size=32,
        quant_kv=False, kv_block_size=8,
        plan="uniform:4,kv=8"))
    assert eng.stats()["kv_bits"] == 8
    assert eng.cache["layers"]["k"].dtype == np.int8
    spec = planning.PlanSpec.parse("uniform:4,kv=8")
    assert planning.PlanSpec.from_json(spec.to_json()) == spec
    assert planning.PlanSpec.parse("auto:q4,kv=auto").solved is False
    # int8 KV prices below f32: more blocks per byte budget
    k8 = planning.kv_pool_blocks(1 << 20, 8, 2, 4, 64, 8)
    k32 = planning.kv_pool_blocks(1 << 20, 8, 2, 4, 64, 32)
    assert k8 > k32


def test_kv_auto_resolves_via_sensitivity_probe(tiny):
    """kv=auto makes the spec unsolved; Planner.solve probes per-layer
    KV quantization error and pins a concrete 8 or 32."""
    from repro import planning
    cfg, params = tiny
    plan = planning.PlanSpec.parse("uniform:4,kv=auto")
    result = planning.Planner(params, cfg, plan).solve()
    assert result.spec.kv_bits in (8, 32)
    assert result.spec.solved
    sens = result.kv_sensitivity
    assert sens is not None and sens["relative"] >= 0
    assert len(sens["per_layer"]) == lm.n_scan_blocks(cfg)
