"""Compressed gradient all-reduce with error feedback.

Data-parallel gradient exchange is the bandwidth hot spot at 512 chips;
the same int8 + per-tensor-scale format SAIL uses for the KV cache would
cut the all-reduce bytes 4x.  This module emulates that exchange's
*numerics* at the XLA level: each step quantizes ``grad + err`` to int8
codes + a per-tensor scale, reduces the dequantized values (``pmean``
over f32 — XLA picks the wire format, so the 4x byte cut is a property
of a backend that reduces the codes directly, not of this lowering),
and keeps the residual locally in a persistent error-feedback state
(1-bit-Adam style), so the *time-averaged* applied gradient is unbiased.
Use it to validate convergence under compression before committing to a
custom int8 collective.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh


def init_error_state(grads):
    """Zero residual matching the gradient pytree."""
    return jax.tree_util.tree_map(
        lambda g: jnp.zeros(getattr(g, "shape", ()), jnp.float32), grads)


def _quantize_dequantize(x: jax.Array) -> jax.Array:
    absmax = jnp.max(jnp.abs(x))
    scale = jnp.where(absmax == 0, 1.0, absmax) / 127.0
    codes = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return codes.astype(jnp.float32) * scale


def make_compressed_allreduce(mesh: Mesh, axes: Sequence[str], specs):
    """Build ``fn(grads, err) -> (mean_grads, new_err)``.

    ``specs``: pytree of PartitionSpecs matching the gradient tree (how
    each per-device gradient shard is laid out).  The mean is taken over
    ``axes``; what crosses the interconnect is the int8-quantized
    ``grad + err``, and the residual stays on-device.
    """
    axes = tuple(axes)

    def shard_fn(grads, err):
        def one(g, e):
            x = g.astype(jnp.float32) + e
            y = _quantize_dequantize(x)
            mean = jax.lax.pmean(y, axes)
            return mean, x - y
        pairs = jax.tree_util.tree_map(one, grads, err)
        mean = jax.tree_util.tree_map(lambda p: p[0], pairs,
                                      is_leaf=lambda p: isinstance(p, tuple))
        new_err = jax.tree_util.tree_map(lambda p: p[1], pairs,
                                         is_leaf=lambda p: isinstance(p, tuple))
        return mean, new_err

    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=(specs, specs),
                       out_specs=(specs, specs))
    return jax.jit(fn)
