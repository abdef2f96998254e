"""Compile-only checks for a TPU v5e: the LUT-GEMV kernels and the
serving engine's decode and prefill steps.

The TPU compiler is installed beside the CPU backend, so a described (not
attached) ``v5e:2x2`` topology compiles the kernels exactly as the chip
would: unsupported lowerings and scoped-VMEM overruns fail here, at no
chip time.  Shapes are ``tinymistral_248m``'s quantized matrices at decode
(M=8) and prefill (M=512) batch, with the blocks ``pick_blocks`` chooses.
The decode step is compiled at the widths the engine serves it with in the
``tinymistral.decode.q4a8`` and ``mellum2.code.q4a8`` benchmark cells, and
its optimized HLO is held to updating the paged KV pool in place and to
reading it in place through the decode attention kernel.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as C
from repro import planning
from repro.core.quant import words_per_group
from repro.kernels.lut_gemv.kernel import (lut_matmul_int_pallas,
                                           lut_matmul_pallas)
from repro.kernels.lut_gemv.ops import _round_up, pick_blocks
from repro.models import lm
from repro.models.sail_linear import QuantPolicy, quantize_params
from repro.serving.engine import Engine, EngineConfig

G = 128
# (K, N) of every quantized matrix of tinymistral_248m: wk/wv, wq/wo,
# w_down, w_gate/w_up, lm_head (vocab 32005 pads to 32256)
SHAPES = [(1024, 256), (1024, 1024), (4096, 1024), (1024, 4096),
          (1024, 32005)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU compiler otherwise writes its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(one_chip, m, k, n, bits, int_act):
    """Lower and compile one kernel call as ``ops.lut_matmul`` makes it."""
    bm, bn, bk = pick_blocks(m, n, k, bits, G, int_act=int_act)
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    weight = [sds((kp // G * words_per_group(bits, G), np_), jnp.uint32),
              sds((kp // G, np_), jnp.float32),
              sds((1 << bits,), jnp.float32)]
    blocks = dict(bits=bits, group_size=G, k=kp, bm=bm, bn=bn, bk=bk,
                  interpret=False)
    if int_act:
        fn = functools.partial(lut_matmul_int_pallas, abits=8, **blocks)
        args = [sds((mp, kp), jnp.int32), sds((mp, 1), jnp.float32)]
    else:
        fn = functools.partial(lut_matmul_pallas, **blocks)
        args = [sds((mp, kp), jnp.float32)]
    return jax.jit(fn).lower(*args, *weight).compile()


@pytest.mark.parametrize("int_act", [False, True], ids=["f32act", "int8act"])
@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("kn", SHAPES, ids=lambda kn: f"K{kn[0]}xN{kn[1]}")
def test_lut_gemv_compiles_for_v5e_decode(one_chip, kn, bits, int_act):
    compiled = _compile(one_chip, 8, *kn, bits, int_act)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("int_act", [False, True], ids=["f32act", "int8act"])
@pytest.mark.parametrize("kn", [(1024, 4096), (4096, 1024)],
                         ids=lambda kn: f"K{kn[0]}xN{kn[1]}")
def test_lut_gemv_compiles_for_v5e_prefill(one_chip, kn, int_act):
    compiled = _compile(one_chip, 512, *kn, 4, int_act)
    assert "tpu_custom_call" in compiled.as_text()


# --- the engine's decode step: the paged KV pool updated in place ----------

# ops that would move a whole pool (or a layer of it) through memory
POOL_MOVES = ("copy", "copy-start", "copy-done", "slice", "slice-start",
              "slice-done", "dynamic-slice", "dynamic-update-slice")
_INSTR = re.compile(r"\s*(?:ROOT )?%?[\w.\-]+ = (.+?) ([a-z][\w\-]*)\(")


def pool_moves(hlo: str, num_blocks: int) -> list:
    """Instructions of an optimized HLO module among ``POOL_MOVES`` whose
    result shape carries the pool's block axis (``num_blocks``)."""
    axis = re.compile(rf"[\[,]{num_blocks}[\],]")
    return [line.strip() for line in hlo.splitlines()
            if (m := _INSTR.match(line)) and m.group(2) in POOL_MOVES
            and axis.search(m.group(1))]


def attention_reads_pool_in_place(hlo: str, batch: int, cols: int,
                                   block: int, row: int) -> bool:
    """The decode attention kernel is on the path, in the ``attn`` scope
    (``op_name`` metadata the trace's scope reader attributes by), and no
    gathered ``[batch, cols, block, row]`` view of the pool is left."""
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and "paged_decode_attention" in line]
    gathered = re.compile(rf"\[{batch},{cols},{block},{row}\]")
    return (len(kernels) == 1 and "/attn/" in kernels[0]
            and not gathered.search(hlo))


def pool_bytes(cache) -> int:
    return sum(a.size * np.dtype(a.dtype).itemsize
               for a in jax.tree_util.tree_leaves(cache["layers"]))


def _lower_decode(fn, params, cache, cfg, batch, table_cols, **place):
    """Lower ``fn`` (an ``lm`` decode step) as ``Engine.step`` calls it:
    int8 KV, an active mask and block tables."""
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, **place)
    return fn.lower(params, sds((batch, 1), jnp.int32), cache, cfg,
                    quant_kv=True, active_mask=sds((batch,), jnp.bool_),
                    block_tables=sds((batch, table_cols), jnp.int32))


def _cell_shapes(cfg, batch, num_blocks, block, one_chip):
    """Shapes of a cell's ``uniform:4a8`` weights and int8 paged pool,
    placed on the described chip."""
    policy = planning.PlanSpec.parse("uniform:4a8").to_policy(
        QuantPolicy(bits=4, group_size=128, min_size=1024))
    params = jax.eval_shape(
        lambda key: quantize_params(lm.init_params(key, cfg), policy)[0],
        jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: lm.init_paged_cache(
        None, cfg, batch, num_blocks, block, quant_kv=True))
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip)
    return jax.tree_util.tree_map(place, (params, cache))


def test_engine_decode_step_updates_pool_in_place_for_v5e(one_chip):
    """The decode program of the ``tinymistral.decode.q4a8`` cell (batch
    32, ``cache_len`` 3072, block 16, int8 KV, ``uniform:4a8``; 6145
    blocks with the trash block), as the engine donates its pool to it:
    no op copies, slices or rewrites a pool-sized array, and the whole
    pool aliases input to output."""
    cfg = C.get_config("tinymistral_248m")
    batch, cache_len, block = 32, 3072, 16
    cols = cache_len // block
    num_blocks = batch * cols + 1
    params, cache = _cell_shapes(cfg, batch, num_blocks, block, one_chip)
    compiled = _lower_decode(lm.donating(lm.decode_step), params, cache,
                             cfg, batch, cols, sharding=one_chip).compile()
    hlo = compiled.as_text()
    assert pool_moves(hlo, num_blocks) == []
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= pool_bytes(cache) > 0.6e9)
    assert attention_reads_pool_in_place(hlo, batch, cols, block,
                                         cfg.n_kv * cfg.head_dim)


def test_engine_prefill_updates_pool_in_place_for_v5e(one_chip):
    """The prefill program of the ``tinymistral.decode.q4a8`` cell at its
    smallest admission shape (one prompt padded to 128; the cell's pool,
    int8 KV, ``uniform:4a8``): one program, whose output pool takes
    over the donated pool's buffers.  (Its scatter still relays the int8
    pools out through another layout and back, as the lone scatter
    program did: PERF.md §7.)"""
    cfg = C.get_config("tinymistral_248m")
    batch, cache_len, block, padded = 32, 3072, 16, 128
    num_blocks = batch * (cache_len // block) + 1
    params, cache = _cell_shapes(cfg, batch, num_blocks, block, one_chip)
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                             sharding=one_chip)
    compiled = lm.prefill_into_blocks.lower(
        params, sds((1, padded)), cache, sds((1,)), sds((padded,)),
        sds((padded,)), cfg, quant_kv=True, lengths=sds((1,))).compile()
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= pool_bytes(cache) > 0.6e9)


@pytest.mark.parametrize("donate", [True, False], ids=["donating", "plain"])
def test_engine_decode_step_updates_pool_in_place_on_cpu(donate):
    """CPU-size twin: the engine's own pool (40 blocks + trash) through
    the donating step aliases with no pool-sized move; the non-donating
    ``lm.decode_step`` keeps its input readable and so copies the pool
    once, at entry."""
    cfg = C.get_smoke("tinymistral_248m")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, EngineConfig(
        batch_size=4, cache_len=64, quantize=True, group_size=32,
        plan="uniform:4a8", kv_block_size=8,
        kv_pool_blocks=40))
    fn = lm.donating(lm.decode_step) if donate else lm.decode_step
    compiled = _lower_decode(fn, eng.params, eng.cache, cfg, 4,
                             eng._mbs).compile()
    moves = pool_moves(compiled.as_text(), 41)
    alias = compiled.memory_analysis().alias_size_in_bytes
    if donate:
        assert moves == [] and alias >= pool_bytes(eng.cache)
    else:
        assert moves and alias == 0


def test_mellum_decode_step_compiles_for_v5e(one_chip):
    """The decode program of the ``mellum2.code.q4a8`` cell (8 layers at
    Mellum2's widths, 64 experts top-8, batch 32, ``cache_len`` 5632,
    block 16, int8 KV, ``uniform:4a8``, the engine's expert counts):
    the routed experts lower to grouped matmuls, the int8 KV pool is
    updated in place, and the step's temporaries leave room on a 16 GB
    chip.  (The float32 scale pools, 64 lanes wide at 4 KV heads and
    block 16, are moved through on-chip memory at each layer, as
    a narrower scale layout would for TinyMistral: PERF.md §7.)"""
    import json
    from pathlib import Path
    from repro.models.common import ModelConfig
    conf = json.loads((Path(__file__).resolve().parents[1] / "bench"
                       / "configs" / "mellum2_12b_a2_5b.json").read_text())
    cfg = ModelConfig(**conf["model"])
    batch, cache_len, block = 32, 5632, 16
    cols = cache_len // block
    num_blocks = batch * cols + 1
    params, cache = _cell_shapes(cfg, batch, num_blocks, block, one_chip)
    assert isinstance(params["blocks"]["moe"]["router"], jax.ShapeDtypeStruct)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    compiled = lm.donating(lm.decode_step).lower(
        params, sds((batch, 1), jnp.int32), cache, cfg, quant_kv=True,
        active_mask=sds((batch,), jnp.bool_),
        block_tables=sds((batch, cols), jnp.int32),
        count_experts=True).compile()
    hlo = compiled.as_text()
    assert "ragged" in hlo
    assert not [line for line in pool_moves(hlo, num_blocks)
                if "s8[" in line]
    assert attention_reads_pool_in_place(hlo, batch, cols, block,
                                         cfg.n_kv * cfg.head_dim)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes(cache) > 1.4e9
    assert mem.temp_size_in_bytes < 4e9


@pytest.mark.parametrize("shape", [(12, 32, 8, 32, 192), (8, 32, 4, 128, 352)],
                         ids=["tinymistral", "mellum2"])
def test_paged_decode_attn_compiles_for_v5e(one_chip, shape):
    """The paged decode attention kernel alone at each cell's widths
    (layers, heads, KV heads, head size, table columns; batch 32, block
    16, the trash block in the pool) compiles.  (Alone, XLA may stage the
    small scale pools in on-chip memory; the decode step tests above hold
    the pool in place where the engine runs the kernel.)"""
    from repro.kernels.decode_attn.kernel import paged_decode_attention
    layers, h, kv, dh, cols = shape
    batch, block = 32, 16
    num_blocks = batch * cols + 1
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    codes = sds((layers, num_blocks, block, kv * dh), jnp.int8)
    scales = sds((layers, num_blocks, kv * block), jnp.float32)
    compiled = jax.jit(paged_decode_attention).lower(
        sds((batch, h, dh), jnp.float32), codes, scales, codes, scales,
        sds((), jnp.int32), sds((batch,), jnp.int32),
        sds((batch, cols), jnp.int32), sds((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
