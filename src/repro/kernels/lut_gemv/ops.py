"""Jitted public wrapper for the LUT-dequant matmul kernel.

Handles padding to block multiples, block-size selection (VMEM budgeting),
the int-activation dispatch (real low-bit serve path whenever the QTensor
carries ``abits``), and the jnp fallback used inside the 512-device
dry-run (same semantics as the kernel; the kernel itself is validated
against ``ref.lut_matmul_ref`` / ``ref.lut_matmul_ref_int``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quant import QTensor, quantize_activations, words_per_group
from repro.kernels.lut_gemv.kernel import (lut_matmul_int_pallas,
                                           lut_matmul_pallas, vmem_bytes)
from repro.kernels.lut_gemv.ref import lut_matmul_ref, lut_matmul_ref_int

# Bytes of VMEM one grid step may hold (``kernel.vmem_bytes``): a quarter
# of v5e's 128 MiB.  Each call sets its scoped limit from its own need.
VMEM_BUDGET = 32 * 2**20
MAX_BK = 2048


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def pick_blocks(m: int, n: int, k: int, bits: int, group_size: int,
                int_act: bool = False):
    """Choose (bm, bn, bk) hardware-aligned and within ``VMEM_BUDGET``.

    MXU wants multiples of (8, 128).  A K block covers whole quant groups,
    and its scales block (``bk // group_size`` rows) must be a multiple of
    8 sublanes unless one block spans all of K.  The working set is
    ``kernel.vmem_bytes``, which counts the dequantized tile as well as
    the pipelined blocks.
    """
    bm = min(_round_up(m, 8), 128)
    bn = min(_round_up(n, 128), 512)
    kg = _round_up(k, group_size)
    step = 8 * group_size              # smallest K block short of all of K
    if kg <= max(MAX_BK, step):
        bk = kg
    else:
        bk = max(step, MAX_BK // step * step)

    def fits():
        return vmem_bytes(bm, bn, bk, bits, group_size,
                          int_act) <= VMEM_BUDGET

    while not fits() and bk % (2 * step) == 0:
        bk //= 2
    while not fits() and bn > 128:
        bn = _round_up(bn // 2, 128)
    while not fits() and bm > 8:
        bm = _round_up(bm // 2, 8)
    return bm, bn, bk


def _pad_weight(qt: QTensor, kp: int, np_: int):
    """Pad packed/scales to the padded (kp, np_) problem."""
    packed, scales = qt.packed, qt.scales
    if kp != qt.k:
        wpg = words_per_group(qt.bits, qt.group_size)
        extra_g = (kp - qt.k) // qt.group_size
        packed = jnp.pad(packed, ((0, extra_g * wpg), (0, 0)))
        scales = jnp.pad(scales, ((0, extra_g), (0, 0)))
    if np_ != qt.n:
        packed = jnp.pad(packed, ((0, 0), (0, np_ - qt.n)))
        scales = jnp.pad(scales, ((0, 0), (0, np_ - qt.n)),
                         constant_values=1.0)
    return packed, scales


def lut_matmul(x: jax.Array, qt: QTensor, out_dtype=jnp.float32,
               backend: str = "pallas",
               interpret: Optional[bool] = None) -> jax.Array:
    """y[M, N] = x[M, K] @ dequant(qt), the SAIL serving matmul.

    backend: "pallas" (TPU kernel; compiled on TPU, interpret mode
    elsewhere when ``interpret`` is None) or "jnp" (pure-jnp
    same-semantics fallback).

    When ``qt.abits`` is set and ``x`` is floating, activations are
    quantized per token (``quantize_activations``) and the integer
    LUT-GEMV path runs — the executed datapath matches the ``abits``
    semantics the allocator priced, with fake-quant surviving only as the
    calibration probe.
    """
    if qt.abits is not None and jnp.issubdtype(x.dtype, jnp.floating):
        x_q, x_scale = quantize_activations(x, qt.abits)
        return lut_matmul_quantized(x_q, x_scale, qt, out_dtype=out_dtype,
                                    backend=backend, interpret=interpret)
    if backend == "jnp":
        return lut_matmul_ref(x, qt, out_dtype)
    m, k = x.shape
    n = qt.n
    bm, bn, bk = pick_blocks(m, n, k, qt.bits, qt.group_size)
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)

    xx = jnp.pad(x, ((0, mp - m), (0, kp - k))) if (mp, kp) != (m, k) else x
    packed, scales = _pad_weight(qt, kp, np_)

    y = lut_matmul_pallas(xx, packed, scales, qt.codebook, bits=qt.bits,
                          group_size=qt.group_size, k=kp, bm=bm, bn=bn,
                          bk=bk, out_dtype=out_dtype, interpret=interpret)
    return y[:m, :n]


def lut_matmul_quantized(x_q: jax.Array, x_scale: jax.Array, qt: QTensor,
                         out_dtype=jnp.float32, backend: str = "pallas",
                         interpret: Optional[bool] = None) -> jax.Array:
    """y[M, N] = (x_q @ dequant(qt)) * x_scale — the int-activation path.

    x_q int32 ``abits``-bit codes, x_scale f32 [M, 1], both straight from
    ``quant.quantize_activations``.  Padding uses zero codes (contribute
    exactly 0 to the dot) so padded and unpadded results agree bit-for-bit
    on the valid slice.
    """
    abits = qt.abits if qt.abits is not None else 8
    if backend == "jnp":
        return lut_matmul_ref_int(x_q, x_scale, qt, out_dtype)
    m, k = x_q.shape
    n = qt.n
    bm, bn, bk = pick_blocks(m, n, k, qt.bits, qt.group_size, int_act=True)
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)

    if (mp, kp) != (m, k):
        x_q = jnp.pad(x_q, ((0, mp - m), (0, kp - k)))
        x_scale = jnp.pad(x_scale, ((0, mp - m), (0, 0)),
                          constant_values=1.0)
    packed, scales = _pad_weight(qt, kp, np_)

    y = lut_matmul_int_pallas(x_q, x_scale, packed, scales, qt.codebook,
                              bits=qt.bits, group_size=qt.group_size, k=kp,
                              abits=abits, bm=bm, bn=bn, bk=bk,
                              out_dtype=out_dtype, interpret=interpret)
    return y[:m, :n]
