"""Compile-only checks of the LUT-GEMV kernels for a TPU v5e.

The TPU compiler is installed beside the CPU backend, so a described (not
attached) ``v5e:2x2`` topology compiles the kernels exactly as the chip
would: unsupported lowerings and scoped-VMEM overruns fail here, at no
chip time.  Shapes are ``tinymistral_248m``'s quantized matrices at decode
(M=8) and prefill (M=512) batch, with the blocks ``pick_blocks`` chooses.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quant import words_per_group
from repro.kernels.lut_gemv.kernel import (lut_matmul_int_pallas,
                                           lut_matmul_pallas)
from repro.kernels.lut_gemv.ops import _round_up, pick_blocks

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
