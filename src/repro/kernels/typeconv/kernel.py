"""Pallas TPU kernel: in-memory parallel int->f32 (SAIL Algorithm 1).

Every VPU lane executes the paper's bitline algorithm in lockstep — the
direct analogue of 512 bitlines converting in parallel: cumulative-OR
leading-one detection, 5-bit ripple popcount for the exponent, bit-reversed
multiply for mantissa alignment, then sign/exponent/mantissa OR-assembled
and bitcast to float32.  No arithmetic float conversion instruction is used
inside the kernel body (only shifts / and / or / xor / integer mul), so the
kernel is faithful to what the C-SRAM performs.

Used fused at the tail of the serving path to keep dequantization off the
"CPU" (scalar) path — the paper's motivation for Algorithm 1.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret


def int_to_f32_compute(a: jax.Array, n: int) -> jax.Array:
    """Algorithm-1 body on an int32 array of n-bit signed values.

    Pure bit-ops (shift/and/or/xor/int-mul) + one bitcast — usable both as
    the typeconv kernel body and fused inside other Pallas kernels (the
    LUT-GEMV int-activation path converts its activation block with this,
    mirroring the paper's PIM typeconv feeding the GEMV datapath).
    """
    a = a.astype(jnp.int32)
    sign = (a >> 31) & 1
    mag = jnp.where(sign == 1, -a, a).astype(jnp.uint32)
    nm1 = n - 1

    # lines 2-4: cumulative-OR leading-one mask
    d = jnp.zeros_like(mag)
    c = jnp.zeros_like(mag)
    for i in range(nm1 - 1, -1, -1):
        ai = (mag >> i) & 1
        d = d | ai
        c = c | (d << i)

    # lines 5-11: 5-bit ripple popcount of C -> biased exponent
    s = [jnp.zeros_like(mag) for _ in range(5)]
    for i in range(nm1):
        carry = (c >> i) & 1
        for j in range(5):
            c1 = s[j] & carry
            s[j] = s[j] ^ carry
            carry = c1
    popc = s[0] | (s[1] << 1) | (s[2] << 2) | (s[3] << 3) | (s[4] << 4)
    biased = popc + jnp.uint32(126)

    # lines 16-17: n-bit reverse of C+1 = 2^k, k = leading zeros; align
    cp1 = c + 1
    rev = jnp.zeros_like(mag)
    for i in range(n):
        rev = rev | (((cp1 >> i) & 1) << (n - 1 - i))
    aligned = (mag * rev) & jnp.uint32((1 << nm1) - 1)

    r = (sign.astype(jnp.uint32) << 31) | (biased << 23)
    if nm1 >= 2:
        mant = aligned & jnp.uint32((1 << (nm1 - 1)) - 1)
        r = r | (mant << (23 - (nm1 - 1)))
    r = jnp.where(mag == 0, jnp.uint32(0), r)
    return jax.lax.bitcast_convert_type(r, jnp.float32)


def _typeconv_kernel(a_ref, o_ref, *, n: int):
    o_ref[...] = int_to_f32_compute(a_ref[...], n)


@functools.partial(jax.jit, static_argnames=("n", "block", "interpret"))
def int_to_f32_pallas(a: jax.Array, n: int = 25, block: int = 512,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Vectorized Algorithm 1 over a 2D array [R, C] (R % 8 == 0 padded by
    ops.py; C % 128 == 0).  ``interpret=None`` takes the platform's choice
    (``repro.kernels.default_interpret``)."""
    if interpret is None:
        interpret = default_interpret()
    r, c = a.shape
    grid = (r // 8, c // block) if c % block == 0 else (r // 8, 1)
    bc = block if c % block == 0 else c
    return pl.pallas_call(
        functools.partial(_typeconv_kernel, n=n),
        grid=grid,
        in_specs=[pl.BlockSpec((8, bc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((8, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.float32),
        interpret=interpret,
    )(a)
