"""Tensor-parallel quantized serving (PR 10): partition-spec arithmetic,
shard_map bit-exactness properties, wire-cost regimes, and tp=2-vs-tp=1
greedy token identity.  Multi-device cases run in a subprocess with 8
fake host devices (the main process must keep 1 device for the smoke
tests)."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# spec arithmetic (pure, single device)
# ---------------------------------------------------------------------------

def test_serving_param_spec_rules():
    from jax.sharding import PartitionSpec as P
    from repro.serving.distributed import serving_param_spec

    # column-parallel: output dim on the model axis; quantized companions
    # (packed [(K/G)*wpg, N], scales [K/G, N]) follow the parent matrix
    assert serving_param_spec("['blocks']['attn']['wq']",
                              (64, 128)) == P(None, "model")
    assert serving_param_spec("['blocks']['attn']['wq'].packed",
                              (16, 128)) == P(None, "model")
    assert serving_param_spec("['blocks']['attn']['wq'].scales",
                              (2, 128)) == P(None, "model")
    assert serving_param_spec("['blocks']['mlp']['w_up'].packed",
                              (2, 16, 128)) == P(None, None, "model")
    # row-parallel: reduction dim on the model axis
    assert serving_param_spec("['blocks']['attn']['wo']",
                              (128, 64)) == P("model", None)
    assert serving_param_spec("['blocks']['mlp']['w_down'].packed",
                              (32, 64)) == P("model", None)
    assert serving_param_spec("['blocks']['mlp']['w_down'].scales",
                              (4, 64)) == P("model", None)
    # stacked-layer leading dim rides through unsharded
    assert serving_param_spec("['blocks']['attn']['wq']",
                              (2, 64, 128)) == P(None, None, "model")
    # codebooks and 1-D params replicate
    assert serving_param_spec("['blocks']['attn']['wq'].codebook",
                              (16,)) == P(None)
    assert serving_param_spec("['final_norm']['scale']", (64,)) == P(None)
    # serving divergence from the training rule: embeddings and lm_head
    # replicate so every shard computes the full logits row
    assert serving_param_spec("['embed']", (256, 64)) == P(None, None)
    assert serving_param_spec("['lm_head']", (64, 256)) == P(None, None)


def test_trim_spec_arithmetic():
    """_trim_spec drops axes the mesh lacks or that don't divide the dim
    — exercised standalone on a fake (1, 2) mesh so the arithmetic is
    covered without any devices."""
    from jax.sharding import PartitionSpec as P
    from repro.dist.sharding import _trim_spec

    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((1, 2), dtype=object)

    mesh = FakeMesh()
    # dividing dims keep their axis
    assert _trim_spec(P(None, "model"), (64, 128), mesh) == P(None, "model")
    assert _trim_spec(P("model", None), (4, 6), mesh) == P("model", None)
    # a non-dividing dim drops the axis (odd group count vs 2 shards)
    assert _trim_spec(P("model", None), (3, 8), mesh) == P(None, None)
    # an axis the mesh lacks drops too
    assert _trim_spec(P("pod", "model"), (8, 8), mesh) == P(None, "model")
    # rank fixup: short specs pad with None, long specs truncate
    assert _trim_spec(P("model"), (4, 6), mesh) == P("model", None)
    assert _trim_spec(P(None, "model", None), (4, 6), mesh) == P(None, "model")
    # size-1 axes always divide
    assert _trim_spec(P("data", "model"), (5, 4), mesh) == P("data", "model")


def test_tp_supported_and_local_config():
    import repro.configs as C
    from repro.serving.distributed import local_config, tp_supported

    cfg = C.get_smoke("tinymistral_248m")   # 8 heads, 2 kv, d_ff 128
    assert tp_supported(cfg, 1) is None
    assert tp_supported(cfg, 2) is None
    assert "n_kv" in tp_supported(cfg, 4)          # n_kv=2 % 4
    assert "n_heads" in tp_supported(cfg, 3)
    moe = dataclasses.replace(cfg, family="moe")
    assert "family" in tp_supported(moe, 2)
    biased = dataclasses.replace(cfg, attention_bias=True)
    assert "bias" in tp_supported(biased, 2)

    lcfg = local_config(cfg, 2)
    assert lcfg.n_heads == cfg.n_heads // 2
    assert lcfg.n_kv == cfg.n_kv // 2
    assert lcfg.d_ff == cfg.d_ff // 2
    # d_head is pinned: it defaults to d_model // n_heads and must not
    # change when n_heads shrinks
    assert lcfg.head_dim == cfg.head_dim
    assert local_config(cfg, 1) is cfg


def test_shard_alignment_and_localize():
    import jax.numpy as jnp
    from repro.core import quant
    from repro.serving.distributed import (localize_params,
                                           shard_alignment_error)

    w = jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32) / 100.0
    tree = {"blocks": {"attn": {"wo": quant.quantize(w, 4, 32),
                                "wq": quant.quantize(w, 4, 32)},
                       "norm": {"scale": jnp.ones((64,))}}}
    # k=64, G=32 -> 2 groups: divides tp=2
    assert shard_alignment_error(tree, 2) is None
    assert shard_alignment_error(tree, 1) is None
    # G=64 -> 1 group on the row-parallel leaf: cannot split K across 2
    bad = {"blocks": {"attn": {"wo": quant.quantize(w, 4, 64)}}}
    err = shard_alignment_error(bad, 2)
    assert err is not None and "wo" in err
    # column-parallel leaves never constrain K
    ok = {"blocks": {"attn": {"wq": quant.quantize(w, 4, 64)}}}
    assert shard_alignment_error(ok, 2) is None

    local = localize_params(tree, 2)
    assert local["blocks"]["attn"]["wo"].k == 32       # row-parallel: K/tp
    assert local["blocks"]["attn"]["wq"].k == 64       # column: full K
    assert localize_params(tree, 1) is tree


# ---------------------------------------------------------------------------
# plan grammar / schema
# ---------------------------------------------------------------------------

def test_planspec_tp_wire_roundtrip():
    from repro.planning import PlanSpec

    spec = PlanSpec.parse("uniform:4a8,tp=2,wire=8")
    assert spec.tp == 2 and spec.wire == 8
    assert spec.solved
    assert PlanSpec.parse(spec.format()) == spec
    assert PlanSpec.from_json(spec.to_json()) == spec

    auto = PlanSpec.parse("auto:q4a8,tp=auto")
    assert auto.tp == "auto"
    assert not auto.solved               # needs the Planner to pin a count
    assert PlanSpec.parse(auto.format()) == auto

    # plans that never mention tp/wire serialize without the keys, so
    # pre-PR-10 spec hashes (and saved plan.json files) are preserved
    plain = PlanSpec.parse("uniform:4a8")
    assert plain.tp is None and plain.wire is None
    assert "tp" not in plain.to_json() and "wire" not in plain.to_json()
    assert "tp=" not in plain.format()

    with pytest.raises(ValueError):
        PlanSpec.parse("uniform:4,wire=16")
    with pytest.raises(ValueError):
        PlanSpec.parse("uniform:4,tp=0")


# ---------------------------------------------------------------------------
# wire-cost model (pure, single device)
# ---------------------------------------------------------------------------

def _smoke_setup():
    import jax
    import repro.configs as C
    from repro.models import lm
    from repro.models.sail_linear import QuantPolicy

    cfg = C.get_smoke("tinymistral_248m")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params, QuantPolicy(bits=4, group_size=32, min_size=1024)


def test_cost_model_regime_switch():
    """Sweeping the link bandwidth walks the plan through the wire-bound
    regime into compute/DRAM-bound — the transition is monotone and the
    sharded terms are exactly 1/tp of the single-device ones."""
    from repro import planning

    cfg, params, policy = _smoke_setup()
    elems = planning.tp_allreduce_elems(cfg)
    assert elems == 2 * cfg.n_layers * cfg.d_model

    base = planning.DecodeCostModel(batch=8)
    one = base.evaluate(params, policy)
    assert one.t_wire == 0.0 and one.bound in ("compute", "dram")

    bounds = []
    for bw in (1e5, 1e7, 1e9, 1e12, 1e15):
        tp2 = planning.DecodeCostModel(batch=8, tp=2, wire_bits=32,
                                       allreduce_elems=elems, link_bw=bw)
        cost = tp2.evaluate(params, policy)
        bounds.append(cost.bound)
        # sharding divides compute and DRAM exactly; the wire term is
        # untouched by the bit allocation
        assert cost.t_compute == pytest.approx(one.t_compute / 2)
        assert cost.t_dram == pytest.approx(one.t_dram / 2)
    assert bounds[0] == "wire"
    assert bounds[-1] in ("compute", "dram")
    first_free = bounds.index(bounds[-1])
    assert all(b == "wire" for b in bounds[:first_free])
    assert all(b != "wire" for b in bounds[first_free:])

    # wire=8 moves a quarter of the bytes of wire=32
    kw = dict(batch=8, tp=2, allreduce_elems=elems, link_bw=1e9)
    t32 = planning.DecodeCostModel(wire_bits=32, **kw).t_wire()
    t8 = planning.DecodeCostModel(wire_bits=8, **kw).t_wire()
    assert t8 == pytest.approx(t32 / 4)


def test_budgets_wire_bound_unreachable():
    """No bit allocation fixes a wire-bound plan: budgets() must refuse
    instead of handing the solver an unmeetable target."""
    from repro import planning

    cfg, _, _ = _smoke_setup()
    elems = planning.tp_allreduce_elems(cfg)
    slo = planning.Slo(1000.0, batch=8)
    choked = planning.DecodeCostModel(batch=8, tp=2, wire_bits=32,
                                      allreduce_elems=elems, link_bw=1e3)
    with pytest.raises(ValueError, match="wire-bound"):
        choked.budgets(slo)
    # per-shard budgets scale by the shard count once the wire fits
    fast = planning.DecodeCostModel(batch=8, tp=2, wire_bits=32,
                                    allreduce_elems=elems, link_bw=1e12)
    single = planning.DecodeCostModel(batch=8)
    b2, b1 = fast.budgets(slo), single.budgets(slo)
    assert b2.cycle_budget == pytest.approx(2 * b1.cycle_budget)


def test_planner_resolves_tp_auto():
    from repro import planning

    cfg, params, policy = _smoke_setup()
    plan = planning.PlanSpec.parse("uniform:4a8,tp=auto")
    planner = planning.Planner(params, cfg, plan, base=policy)

    # no SLO: nothing to meet, sharding buys nothing -> tp=1
    assert planner._resolve_tp(plan, None).tp == 1
    # trivially met target: the smallest grid point wins
    assert planner._resolve_tp(plan, planning.Slo(1e-6, batch=8)).tp == 1
    # unmeetable target: the sweep runs off the grid end
    worst = planner._resolve_tp(plan, planning.Slo(1e15, batch=8))
    assert worst.tp == planning.Planner.TP_GRID[-1]
    # resolving through solve() pins the count and the result is solved
    solved = planner.solve(slo=planning.Slo(1e-6, batch=8)).spec
    assert isinstance(solved.tp, int)
    assert solved.solved


# ---------------------------------------------------------------------------
# shard_map properties (subprocess, 8 fake devices)
# ---------------------------------------------------------------------------

def test_lut_matmul_shard_map_bitexact():
    """Column- and row-parallel shard_map runs of the LUT matmul match
    the single-device kernel bit-for-bit across wbits x abits.

    The data is constructed integer-valued (integer codebook, unit group
    scales, activation rows pinned to the quantizer's qmax so the
    per-token scale is exactly 1.0): every product and partial sum stays
    below 2^24, f32 arithmetic is exact, and any split of the reduction
    must agree to the bit."""
    res = run_subprocess(textwrap.dedent("""
        import dataclasses, json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import quant
        from repro.kernels.lut_gemv import ops
        from repro.launch.mesh import make_mesh

        B, K, N, G, TP = 4, 128, 64, 32, 2
        mesh = make_mesh((1, TP), ("data", "model"))
        rng = np.random.default_rng(0)

        def qspec(qt, pk, sc):
            leaves, treedef = jax.tree_util.tree_flatten(qt)
            assert len(leaves) == 3        # packed, scales, codebook
            return jax.tree_util.tree_unflatten(treedef, [pk, sc, P(None)])

        def integer_qtensor(wb, ab):
            # integer codebook + unit scales -> dequant is exact integers
            if wb == 1:
                book = jnp.asarray([-1.0, 1.0], jnp.float32)
            else:
                book = (jnp.arange(1 << wb, dtype=jnp.float32)
                        - float(1 << (wb - 1)))
            codes = jnp.asarray(
                rng.integers(0, 1 << wb, size=(K, N)), jnp.uint32)
            return quant.QTensor(
                packed=quant.pack_grouped(codes, wb, G),
                scales=jnp.ones((K // G, N), jnp.float32),
                codebook=book, bits=wb, group_size=G, k=K, abits=ab)

        out = {}
        for wb in (1, 2, 3, 4, 8):
            for ab in (4, 6, 8):
                qt = integer_qtensor(wb, ab)
                qmax = (1 << (ab - 1)) - 1
                x = rng.integers(-qmax, qmax + 1,
                                 size=(B, K)).astype(np.float32)
                x[:, 0] = qmax            # row absmax == qmax -> scale 1.0
                x = jnp.asarray(x)

                single = ops.lut_matmul(x, qt, backend="jnp")

                col = jax.shard_map(
                    lambda x, q: ops.lut_matmul(x, q, backend="jnp"),
                    mesh=mesh,
                    in_specs=(P(None, None),
                              qspec(qt, P(None, "model"), P(None, "model"))),
                    out_specs=P(None, "model"), check_vma=False)(x, qt)

                xq, xs = quant.quantize_activations(x, ab)
                single_int = ops.lut_matmul_quantized(
                    xq, xs, qt, backend="jnp")

                def row_body(xq, xs, q):
                    local = dataclasses.replace(q, k=q.k // TP)
                    part = ops.lut_matmul_quantized(
                        xq, xs, local, backend="jnp")
                    return jax.lax.psum(part, "model")

                row = jax.shard_map(
                    row_body, mesh=mesh,
                    in_specs=(P(None, "model"), P(None, None),
                              qspec(qt, P("model", None), P("model", None))),
                    out_specs=P(None, None), check_vma=False)(xq, xs, qt)

                key = f"w{wb}a{ab}"
                out[key] = {
                    "scale_one": bool(jnp.all(xs == 1.0)),
                    "col": bool(np.array_equal(np.asarray(single),
                                               np.asarray(col))),
                    "row": bool(np.array_equal(np.asarray(single_int),
                                               np.asarray(row))),
                    "int_matches_float": bool(np.array_equal(
                        np.asarray(single), np.asarray(single_int))),
                }
        print(json.dumps(out))
    """))
    for key, cell in res.items():
        assert cell["scale_one"], f"{key}: activation scale not exactly 1"
        assert cell["col"], f"{key}: column-parallel diverged"
        assert cell["row"], f"{key}: row-parallel diverged"
        assert cell["int_matches_float"], f"{key}: int path diverged"


def test_int8_wire_allreduce():
    """wire=8 all-reduce: error bounded by the int8 rounding budget and
    bit-deterministic per seed; wire=32 matches the exact sum."""
    res = run_subprocess(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.dist.sharding import tp_all_reduce, tp_context
        from repro.launch.mesh import make_mesh

        TP, B, D = 2, 8, 64
        mesh = make_mesh((1, TP), ("data", "model"))
        parts = jax.random.normal(jax.random.PRNGKey(7), (TP, B, D))
        exact = np.asarray(parts).sum(axis=0)

        def run(wire):
            def body(p):
                with tp_context("model", wire):
                    return tp_all_reduce(p[0])
            fn = jax.shard_map(body, mesh=mesh,
                               in_specs=(P("model", None, None),),
                               out_specs=P(None, None), check_vma=False)
            return np.asarray(fn(parts))

        r8a, r8b, r32 = run(8), run(8), run(32)
        # one int8 round-off per shard, each at most scale/2
        budget = sum(np.abs(np.asarray(parts[i])).max() / 127.0
                     for i in range(TP))
        print(json.dumps({
            "exact32": bool(np.array_equal(r32, exact)),
            "deterministic": bool(np.array_equal(r8a, r8b)),
            "max_err": float(np.abs(r8a - exact).max()),
            "budget": float(budget),
            "nontrivial": bool(np.abs(r8a - exact).max() > 0.0),
        }))
    """))
    assert res["exact32"]
    assert res["deterministic"]
    assert res["max_err"] <= res["budget"]
    assert res["nontrivial"]        # the compressor actually ran


def test_engine_tp_identity_ring_and_paged():
    """tp=2 greedy decode is token-identical to tp=1 through the full
    engine (continuous batching, int8 KV) on both the ring and the paged
    pool, and the stats surface the wire accounting."""
    res = run_subprocess(textwrap.dedent("""
        import json
        import jax
        from repro.configs import get_smoke
        from repro.models import lm
        from repro.serving.engine import Engine, EngineConfig

        cfg = get_smoke("tinymistral_248m")
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        PROMPTS = [[3, 5, 7, 2, 9], [4, 4, 1], [8, 2, 6, 1, 1, 5], [7]]

        def run(tp, paged):
            kw = dict(batch_size=4, cache_len=64, quantize=True, ql=4,
                      group_size=32, min_size=1024, quant_kv=True,
                      tp=tp, wire=32)
            if paged:
                kw["kv_block_size"] = 8
            eng = Engine(params, cfg, EngineConfig(**kw))
            for p in PROMPTS:
                eng.submit(list(p), 6)
            eng.run()
            toks = [eng.completions[u].tokens
                    for u in sorted(eng.completions)]
            return toks, eng.stats()

        out = {}
        for paged in (False, True):
            t1, s1 = run(1, paged)
            t2, s2 = run(2, paged)
            name = "paged" if paged else "ring"
            out[name + "_match"] = t1 == t2
            out[name + "_nonempty"] = all(len(t) == 6 for t in t2)
            if not paged:
                out["tp1_stats"] = s1["tp"]
                out["tp_stats"] = s2["tp"]
        print(json.dumps(out))
    """))
    assert res["ring_match"], "tp=2 diverged from tp=1 on the ring pool"
    assert res["paged_match"], "tp=2 diverged from tp=1 on the paged pool"
    assert res["ring_nonempty"] and res["paged_nonempty"]
    assert res["tp1_stats"] is None          # no tp section at tp=1
    tp = res["tp_stats"]
    assert tp["shards"] == 2 and tp["wire_bits"] == 32
    # batch * 2 * L * d_model * 4 bytes * 2(M-1)/M = 4*2*2*64*4*1
    assert tp["allreduce_bytes_per_iter"] == 4096


def test_plan_tp_overrides_engine_knob():
    """A plan carrying tp=/wire= is the precision contract: it overrides
    the EngineConfig knobs, and greedy output under the plan's shards
    matches the same plan's arithmetic at tp=1."""
    res = run_subprocess(textwrap.dedent("""
        import json
        import jax
        from repro.configs import get_smoke
        from repro.models import lm
        from repro.serving.engine import Engine, EngineConfig

        cfg = get_smoke("tinymistral_248m")
        params = lm.init_params(jax.random.PRNGKey(0), cfg)

        def engine(plan):
            return Engine(params, cfg, EngineConfig(
                batch_size=2, cache_len=64, quantize=True, ql=4,
                group_size=32, min_size=1024, quant_kv=True, tp=1,
                plan=plan))

        def run(plan):
            eng = engine(plan)
            eng.submit([3, 1, 4, 1, 5], 5)
            eng.run()
            return [eng.completions[u].tokens
                    for u in sorted(eng.completions)]

        st = engine("uniform:4a8,tp=2,wire=8").stats()["tp"]
        print(json.dumps({
            "shards": st["shards"], "wire_bits": st["wire_bits"],
            "match": run("uniform:4a8,tp=2") == run("uniform:4a8"),
        }))
    """))
    assert res["shards"] == 2
    assert res["wire_bits"] == 8
    # like with like: both sides serve uniform:4a8 (8-bit activations)
    # over an exact 32-bit wire, so only the sharding differs
    assert res["match"]
