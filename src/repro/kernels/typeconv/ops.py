"""Public wrapper for the Algorithm-1 conversion kernel (padding + fallback)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.typeconv import int_to_f32 as _core_int_to_f32
from repro.kernels.typeconv.kernel import int_to_f32_pallas


def int_to_f32(a: jax.Array, n: int = 25, backend: str = "pallas",
               interpret: Optional[bool] = None) -> jax.Array:
    """Convert int array (|a| < 2**(n-1), n <= 25) to f32, Algorithm 1.

    backend "jnp" uses the pure-JAX line-by-line implementation from
    repro.core.typeconv; "pallas" runs the TPU kernel (compiled on a TPU,
    interpreted elsewhere when ``interpret`` is None).
    """
    if backend == "jnp":
        return _core_int_to_f32(a.reshape(-1), n).reshape(a.shape)
    shape = a.shape
    flat = a.reshape(-1)
    c = 128
    rows = -(-flat.size // c)
    rows_p = -(-rows // 8) * 8
    pad = rows_p * c - flat.size
    a2 = jnp.pad(flat, (0, pad)).reshape(rows_p, c)
    out = int_to_f32_pallas(a2, n=n, block=c, interpret=interpret)
    return out.reshape(-1)[:flat.size].reshape(shape)
