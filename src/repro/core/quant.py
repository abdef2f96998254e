"""Group-wise low-bit quantization for SAIL.

The paper's LUT-GEMV consumes weights quantized at arbitrary precision
(2/3/4/5/6/8-bit, the ``ql`` field of the ``lutmm_1k`` instruction) with
group-wise scales.  This module provides:

  * ``quantize`` / ``dequantize``  — group-wise symmetric or asymmetric
    quantization along the reduction axis (rows of ``W[K, N]``).
  * ``pack_bits`` / ``unpack_bits`` — field packing of b-bit codes into
    uint32 words (``32 // b`` values per word; 3/5/6-bit waste 2 bits/word).
  * ``pack_grouped`` / ``unpack_grouped`` — bit-contiguous group packing
    (``ceil(b*G/32)`` words per group; codes may straddle word boundaries
    so packed bytes are strictly monotone in ``b``).
  * ``QTensor``                    — pytree carrying packed codes + scales +
    codebook, the storage format streamed HBM->VMEM by the Pallas kernel.
  * per-token activation quantization for the integer LUT-GEMV path.

Dequantization supports two modes, mirroring the two LUT flavours:
  * uniform  :  w = scale * (q - zero)            (affine; implicit LUT)
  * codebook :  w = scale * codebook[q]           (explicit 2^bits LUT,
                  the in-VMEM analogue of the paper's C-SRAM-resident LUT)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

SUPPORTED_BITS = (2, 3, 4, 5, 6, 8)

# Precisions the storage/kernel layer handles.  1-bit (sign) weights are a
# kernel-level capability (the LUT formulation supports them for free) but
# stay out of SUPPORTED_BITS: the allocator's candidate set and the policy
# grammar keep the paper's 2..8-bit ``ql`` range.
KERNEL_BITS = (1,) + SUPPORTED_BITS

# Activation precisions the ``lutmm`` instruction parameterizes (the
# second precision field next to ``ql``).  ``None`` anywhere an abits is
# accepted means "serve f32 activations" (no activation quantization).
SUPPORTED_ABITS = (4, 6, 8)


def values_per_word(bits: int) -> int:
    """Number of b-bit codes fully contained per uint32 word."""
    if bits not in KERNEL_BITS:
        raise ValueError(f"bits must be one of {KERNEL_BITS}, got {bits}")
    return 32 // bits


# ---------------------------------------------------------------------------
# Bit packing (axis 0 is always the packed axis)
# ---------------------------------------------------------------------------

def pack_bits(codes: jax.Array, bits: int) -> jax.Array:
    """Pack unsigned b-bit integer ``codes`` along axis 0 into uint32 words.

    codes: integer array [K, ...] with values in [0, 2^bits).  K must be a
    multiple of ``values_per_word(bits)``.  Returns uint32 [K/vpw, ...].
    """
    vpw = values_per_word(bits)
    k = codes.shape[0]
    if k % vpw != 0:
        pad = vpw - k % vpw
        codes = jnp.concatenate(
            [codes, jnp.zeros((pad,) + codes.shape[1:], codes.dtype)], axis=0)
        k = codes.shape[0]
    codes = codes.astype(jnp.uint32)
    grouped = codes.reshape((k // vpw, vpw) + codes.shape[1:])
    shifts = (jnp.arange(vpw, dtype=jnp.uint32) * bits).reshape(
        (1, vpw) + (1,) * (codes.ndim - 1))
    return jnp.bitwise_or.reduce(grouped << shifts, axis=1)


def unpack_bits(packed: jax.Array, bits: int, k: Optional[int] = None) -> jax.Array:
    """Inverse of :func:`pack_bits`.  Returns int32 [K, ...]."""
    vpw = values_per_word(bits)
    mask = jnp.uint32((1 << bits) - 1)
    shifts = (jnp.arange(vpw, dtype=jnp.uint32) * bits).reshape(
        (1, vpw) + (1,) * (packed.ndim - 1))
    codes = (packed[:, None] >> shifts) & mask
    out = codes.reshape((packed.shape[0] * vpw,) + packed.shape[1:])
    if k is not None:
        out = out[:k]
    return out.astype(jnp.int32)


def words_per_group(bits: int, group_size: int) -> int:
    """uint32 words holding one quantization group's codes.

    Packing is bit-contiguous within a group (codes may straddle word
    boundaries), so a group costs exactly ``ceil(bits * G / 32)`` words.
    This makes packed bytes strictly monotone in ``bits`` for every
    group size >= 32 — the old value-aligned layout collapsed 3/4-bit
    (and 5/6-bit) to identical sizes at group 32, flattening Pareto
    sweeps over the bit ladder.
    """
    if bits not in KERNEL_BITS:
        raise ValueError(f"bits must be one of {KERNEL_BITS}, got {bits}")
    return -(-(bits * group_size) // 32)  # ceil


def pack_grouped(codes: jax.Array, bits: int, group_size: int) -> jax.Array:
    """Group-aligned, bit-contiguous packing: each quantization group of
    ``group_size`` codes occupies ``ceil(bits*G/32)`` uint32 words, with
    the codes laid down as a little-endian bitstream (code ``v`` occupies
    stream bits ``[v*bits, (v+1)*bits)``; trailing stream bits zero).

    Groups stay word-aligned so a kernel block covering ``bk`` K-rows
    maps to exactly ``(bk // group_size) * wpg`` packed rows — the TPU
    analogue of SAIL keeping one group's LUT per C-SRAM residency.  When
    ``32 % bits == 0`` the layout coincides with plain value-aligned
    packing.  codes: [K, N] -> packed uint32 [(K//G)*wpg, N].
    """
    k = codes.shape[0]
    if k % group_size != 0:
        raise ValueError(f"K={k} not a multiple of group_size={group_size}")
    wpg = words_per_group(bits, group_size)
    g = k // group_size
    n_slots = wpg * 32  # stream bit positions per group
    grouped = codes.reshape((g, group_size) + codes.shape[1:])
    pad = -(-n_slots // bits) - group_size  # values covering every slot
    if pad:
        grouped = jnp.concatenate(
            [grouped, jnp.zeros((g, pad) + codes.shape[1:], codes.dtype)],
            axis=1)
    grouped = grouped.astype(jnp.uint32)
    t = np.arange(n_slots)
    src = jnp.asarray(t // bits, dtype=jnp.int32)
    sh = jnp.asarray(t % bits, dtype=jnp.uint32).reshape(
        (1, n_slots) + (1,) * (codes.ndim - 1))
    stream = (grouped[:, src] >> sh) & jnp.uint32(1)
    stream = stream.reshape((g, wpg, 32) + codes.shape[1:])
    wshifts = jnp.arange(32, dtype=jnp.uint32).reshape(
        (1, 1, 32) + (1,) * (codes.ndim - 1))
    words = jnp.sum(stream << wshifts, axis=2, dtype=jnp.uint32)
    return words.reshape((g * wpg,) + codes.shape[1:])


def unpack_grouped(packed: jax.Array, bits: int, group_size: int,
                   k: int) -> jax.Array:
    """Inverse of :func:`pack_grouped` -> int32 [K, ...]."""
    wpg = words_per_group(bits, group_size)
    g = k // group_size
    words = packed.reshape((g, wpg) + packed.shape[1:])
    wshifts = jnp.arange(32, dtype=jnp.uint32).reshape(
        (1, 1, 32) + (1,) * (packed.ndim - 1))
    stream = (words[:, :, None] >> wshifts) & jnp.uint32(1)
    stream = stream.reshape((g, wpg * 32) + packed.shape[1:])
    stream = stream[:, :group_size * bits].reshape(
        (g, group_size, bits) + packed.shape[1:])
    bshifts = jnp.arange(bits, dtype=jnp.uint32).reshape(
        (1, 1, bits) + (1,) * (packed.ndim - 1))
    codes = jnp.sum(stream << bshifts, axis=2, dtype=jnp.uint32)
    return codes.reshape((k,) + packed.shape[1:]).astype(jnp.int32)


# ---------------------------------------------------------------------------
# QTensor
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QTensor:
    """SAIL-quantized weight tensor (the HBM storage format).

    Logical weight is ``W[K, N]`` (reduction dim first).  Fields:
      packed   : uint32 [(K//G)*wpg, N] group-aligned packed b-bit codes
      scales   : f32    [K // G, N]     per-group scales
      codebook : f32    [2**bits]       dequant LUT (uniform grid by default)
      bits, group_size, k: static metadata.
      abits    : activation precision this matmul serves at (the lutmm
                 instruction's second precision field); None keeps f32
                 activations.  When set, ``mm``/``einsum_q`` run the real
                 integer path: per-token ``abits`` codes enter the kernel
                 and the scale is applied to the output.
    """
    packed: jax.Array
    scales: jax.Array
    codebook: jax.Array
    bits: int = dataclasses.field(metadata=dict(static=True))
    group_size: int = dataclasses.field(metadata=dict(static=True))
    k: int = dataclasses.field(metadata=dict(static=True))
    abits: Optional[int] = dataclasses.field(
        default=None, metadata=dict(static=True))

    @property
    def n(self) -> int:
        return self.packed.shape[-1]

    @property
    def shape(self):
        return (self.k, self.n)

    def nbytes(self) -> int:
        return (self.packed.size * 4 + self.scales.size * 4
                + self.codebook.size * 4)


def _uniform_codebook(bits: int) -> jnp.ndarray:
    """Symmetric uniform codebook: code q -> q - 2^(b-1) (signed grid)."""
    if bits == 1:
        # sign codebook: the signed grid degenerates to [-1, 0] at 1 bit
        return jnp.asarray([-1.0, 1.0], dtype=jnp.float32)
    qmax = (1 << (bits - 1)) - 1
    grid = jnp.arange(1 << bits, dtype=jnp.float32) - float(1 << (bits - 1))
    # normalise so max |entry| == 1; scale carries the magnitude
    return grid / float(max(qmax, 1))


def nf_codebook(bits: int) -> jnp.ndarray:
    """'NormalFloat'-style non-uniform codebook (beyond-paper option):

    quantiles of a standard normal, normalised to [-1, 1].  The explicit
    codebook LUT is exactly what the C-SRAM stores in SAIL, so non-uniform
    grids come for free in the LUT formulation.
    """
    levels = 1 << bits
    # evenly spaced probabilities avoiding 0/1
    p = (np.arange(levels) + 0.5) / levels
    # inverse normal CDF via numpy (Acklam approximation not needed: use
    # scipy-free erfinv through np)
    q = np.sqrt(2.0) * _erfinv(2 * p - 1)
    q = q / np.abs(q).max()
    return jnp.asarray(q, dtype=jnp.float32)


def _erfinv(x):
    """Vectorised inverse error function (Winitzki approximation, <2e-3)."""
    x = np.clip(x, -0.999999, 0.999999)
    a = 0.147
    ln1mx2 = np.log(1 - x * x)
    t1 = 2 / (np.pi * a) + ln1mx2 / 2
    return np.sign(x) * np.sqrt(np.sqrt(t1 * t1 - ln1mx2 / a) - t1)


def nearest_codes(normed: jax.Array, codebook: jax.Array,
                  uniform: bool) -> jax.Array:
    """Index of the ``codebook`` entry nearest each of ``normed`` (values
    in [-1, 1]), the lowest index on a tie, as int32.

    ``uniform`` (``codebook`` is ``_uniform_codebook(bits)``) rounds in
    closed form: the rounded grid point and its two neighbours are the
    only candidates, compared by the same float32 distances an
    exhaustive search takes, so the codes are the search's, bit for bit,
    without its ``[..., 2**bits]`` distance tensor.  A general codebook
    is searched exhaustively."""
    levels = codebook.shape[-1]
    if not uniform or levels == 2:
        return jnp.argmin(jnp.abs(normed[..., None] - codebook),
                          axis=-1).astype(jnp.int32)
    half = levels // 2
    mid = jnp.round(normed * float(half - 1)).astype(jnp.int32) + half
    best = best_d = None
    for step in (-1, 0, 1):            # ascending: a tie keeps the lower
        c = jnp.clip(mid + step, 0, levels - 1)
        d = jnp.abs(normed - jnp.take(codebook, c))
        if best is None:
            best, best_d = c, d
        else:
            take = d < best_d
            best = jnp.where(take, c, best)
            best_d = jnp.where(take, d, best_d)
    return best


@partial(jax.jit, static_argnums=(2, 3))
def quantize_slice(w, codebook: jax.Array, group_size: int, uniform: bool):
    """One ``w[K, N]`` (a device or host array) to (packed codes, group
    scales), in one compiled call per shape: the float32 copy and its
    rounding live only inside it.  ``codebook`` (float32; the uniform
    one where ``uniform``) is passed in, not built inside, so that the
    compiler cannot fold it into constants that differ from its entries
    in the last bit."""
    k, n = w.shape
    bits = codebook.shape[-1].bit_length() - 1
    wg = w.astype(jnp.float32).reshape(k // group_size, group_size, n)
    scale = jnp.max(jnp.abs(wg), axis=1)  # [K/G, N]
    scale = jnp.where(scale == 0, 1.0, scale)
    # the barrier keeps the division out of the distances' fusion, where
    # it could round otherwise than the division alone
    normed = jax.lax.optimization_barrier(wg / scale[:, None, :])
    codes = nearest_codes(normed, codebook, uniform)
    codes = codes.astype(jnp.uint32).reshape(k, n)
    return pack_grouped(codes, bits, group_size), scale


def quantize(w: jax.Array, bits: int, group_size: int = 128,
             codebook: Optional[jax.Array] = None) -> QTensor:
    """Group-wise quantization of ``w[K, N]`` along K.

    For the uniform codebook this is classic symmetric round-to-nearest;
    for a general codebook it is nearest-codebook-entry assignment with a
    per-group absmax scale.  ``w`` may be a host (NumPy) array: it moves
    to the device inside one compiled call.
    """
    if w.ndim != 2:
        raise ValueError(f"expected W[K, N], got shape {w.shape}")
    k, n = w.shape
    if k % group_size != 0:
        raise ValueError(f"K={k} not a multiple of group_size={group_size}")
    uniform = codebook is None
    codebook = (_uniform_codebook(bits) if uniform
                else codebook.astype(jnp.float32))
    packed, scale = quantize_slice(w, codebook, group_size, uniform)
    return QTensor(packed=packed, scales=scale, codebook=codebook,
                   bits=bits, group_size=group_size, k=k)


def dequantize(qt: QTensor) -> jax.Array:
    """Reconstruct f32 ``W[K, N]`` — the pure-jnp oracle for all kernels."""
    codes = unpack_grouped(qt.packed, qt.bits, qt.group_size, qt.k)  # [K, N]
    vals = qt.codebook[codes]                              # [K, N]
    vals = vals.reshape(qt.k // qt.group_size, qt.group_size, qt.n)
    return (vals * qt.scales[:, None, :]).reshape(qt.k, qt.n)


def quantize_int(w: jax.Array, bits: int, group_size: int = 128):
    """Integer-domain group-wise quantization used by the *faithful*
    bit-serial LUT-GEMV path (core/lut_gemv.py).

    Returns (w_q int32 [K,N] signed codes, scales f32 [K/G, N]) with
    w ~= scales[g] * w_q.
    """
    k, n = w.shape
    qmax = (1 << (bits - 1)) - 1
    wg = w.reshape(k // group_size, group_size, n)
    absmax = jnp.max(jnp.abs(wg), axis=1)
    absmax = jnp.where(absmax == 0, 1.0, absmax)
    scale = absmax / qmax
    wq = jnp.clip(jnp.round(wg / scale[:, None, :]), -qmax - 1, qmax)
    return wq.reshape(k, n).astype(jnp.int32), scale


def quantize_activations(x: jax.Array, bits: int = 8):
    """Per-token (row) symmetric activation quantization.

    x[B, K] -> (x_q int32 in [-2^(b-1)+1, 2^(b-1)-1], scale f32 [B, 1]).

    Under a tensor-parallel shard_map trace the absmax is maxed over the
    model axis: a row-parallel matmul's input is K-sharded, and only the
    global absmax reproduces the unsharded quantization bit-for-bit.
    """
    from repro.dist.sharding import tp_axis_max

    qmax = (1 << (bits - 1)) - 1
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    absmax = tp_axis_max(absmax)
    absmax = jnp.where(absmax == 0, 1.0, absmax)
    scale = absmax / qmax
    xq = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int32)
    return xq, scale


def quantize_kv(x: jax.Array, axis: int = -1):
    """int8 symmetric quantization for the KV cache (per-head-dim absmax).

    Returns (int8 codes, f32 scales broadcastable against codes)."""
    absmax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    absmax = jnp.where(absmax == 0, 1.0, absmax)
    scale = absmax / 127.0
    codes = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return codes, scale.astype(jnp.float32)


def dequantize_kv(codes: jax.Array, scale: jax.Array,
                  dtype=jnp.float32) -> jax.Array:
    return (codes.astype(jnp.float32) * scale).astype(dtype)
