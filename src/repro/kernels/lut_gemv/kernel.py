"""Pallas TPU kernel: LUT-dequant quantized matmul (SAIL LUT-GEMV on TPU).

TPU adaptation of the paper's C-SRAM LUT-GEMV (see DESIGN.md Sec. 2):

  * packed b-bit weight codes stream HBM -> VMEM tile by tile (the DRAM ->
    LLC ping-pong of Fig. 4 is Pallas' grid pipelining, which
    double-buffers the next weight block against current compute);
  * the 2**bits-entry dequant LUT (codebook) is VMEM-resident for the whole
    kernel — built once, reused across every tile, batch row, and K-group,
    which is the paper's central data-reuse property;
  * unpack + LUT gather + group-scale happen entirely in VMEM, feeding the
    MXU with an f32 tile — multiplications never touch the unquantized
    weight in HBM, so HBM bytes drop by ~(16/bits)x exactly as C-SRAM
    computing removes the LLC-external weight traffic.

Two activation flavours, matching the ``lutmm`` instruction's dual
precision fields (``ql`` for weights, abits for activations):

  * ``_lut_matmul_kernel``      — f32 activations (abits None);
  * ``_lut_matmul_int_kernel``  — int activation codes + per-token scales
    from ``quantize_activations``.  The codes are converted in-kernel with
    the paper's Algorithm-1 bitline typeconv (``int_to_f32_compute``) and
    the per-token scale is folded in at the accumulator store, so the
    executed datapath consumes exactly the ``abits`` integers the
    allocator priced — no fake-quant in the serve path.

Grid: (M/bm, N/bn, K/bk) with K innermost (accumulation).  The packed
operand is group-aligned (``pack_grouped``: ``ceil(bits*G/32)`` words per
group, bit-contiguous) so each K-block maps to an integer number of
packed rows.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import words_per_group
from repro.kernels import default_interpret
from repro.kernels.typeconv.kernel import int_to_f32_compute


def _unpack_codes(words, *, bits: int, group_size: int, bk: int, bn: int):
    """Decode one packed (K-block, bn) tile -> int32 codes [bk, bn].

    Mirrors ``quant.unpack_grouped``: each group is a little-endian
    bitstream over ``wpg = ceil(bits*G/32)`` uint32 words, and code ``v``
    occupies stream bits ``[v*bits, (v+1)*bits)``.

    When ``bits * G`` is a multiple of 32 and ``bits`` divides 32, no code
    straddles a word: row ``r`` of the tile is field ``r % (32/bits)`` of
    word ``r // (32/bits)``, so each word row is repeated ``32/bits`` times
    along sublanes and shifted by a per-row amount.  Any other width is
    decoded as a bitstream, one bit-plane per stream bit.  Shifts, masks
    and a signed sum only, so both forms lower on the TPU vector unit.
    """
    mask = jnp.uint32((1 << bits) - 1)
    if 32 % bits == 0 and (bits * group_size) % 32 == 0:
        vpw = 32 // bits
        rows = words.shape[0]
        rep = jnp.broadcast_to(words[:, None, :], (rows, vpw, bn))
        rep = rep.reshape(bk, bn)
        row = jax.lax.broadcasted_iota(jnp.uint32, (bk, bn), 0)
        shifts = (row & jnp.uint32(vpw - 1)) * jnp.uint32(bits)
        return ((rep >> shifts) & mask).astype(jnp.int32)
    groups = bk // group_size
    wpg = words_per_group(bits, group_size)
    words = words.reshape(groups, wpg, bn)
    wshifts = jnp.arange(32, dtype=jnp.uint32)[None, None, :, None]
    stream = (words[:, :, None, :] >> wshifts) & jnp.uint32(1)
    stream = stream.reshape(groups, wpg * 32, bn)[:, :group_size * bits, :]
    stream = stream.reshape(groups, group_size, bits, bn).astype(jnp.int32)
    bshifts = jnp.arange(bits, dtype=jnp.int32)[None, None, :, None]
    return jnp.sum(stream << bshifts, axis=2).reshape(bk, bn)


def _lut_lanes(bits: int) -> int:
    """Lanes of the LUT row: 2**bits entries padded to whole 128-lane
    vregs, so each lookup is an in-vreg lane gather."""
    return 128 * -(-(1 << bits) // 128)


def _lut_lookup(lut_ref, codes, bits: int):
    """codebook[codes] as a lane gather per 128-lane column chunk.

    ``lut_ref`` is the codebook laid along lanes ([1, _lut_lanes(bits)]);
    a LUT wider than one vreg (8-bit: 256 entries) is read as 128-entry
    halves, selected by the code's high bits.  Exact: every output is a
    LUT entry, no arithmetic.
    """
    rows, cols = codes.shape
    halves = _lut_lanes(bits) // 128
    tabs = [jnp.broadcast_to(lut_ref[:, h * 128:(h + 1) * 128], (rows, 128))
            for h in range(halves)]
    out = []
    for c in range(0, cols, 128):
        idx = codes[:, c:c + 128]
        lo = idx & 127
        w = None
        for h, tab in enumerate(tabs):
            g = jnp.take_along_axis(tab, lo, axis=1, mode="promise_in_bounds")
            w = g if w is None else jnp.where((idx >> 7) == h, g, w)
        out.append(w)
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def _dequant_block(packed_ref, scales_ref, lut_ref, *, bits: int,
                   group_size: int, bk: int):
    """LUT dequant of one packed (K-block, bn) tile -> f32 [bk, bn]."""
    bn = packed_ref.shape[-1]
    groups = bk // group_size
    codes = _unpack_codes(packed_ref[...], bits=bits, group_size=group_size,
                          bk=bk, bn=bn)
    w = _lut_lookup(lut_ref, codes, bits)
    w = w.reshape(groups, group_size, bn) * scales_ref[...][:, None, :]
    return w.reshape(bk, bn)


def _lut_matmul_kernel(x_ref, packed_ref, scales_ref, lut_ref, o_ref,
                       acc_ref, *, bits: int, group_size: int, bk: int,
                       n_k: int, out_dtype):
    """One (bm, bn) output tile; accumulates over the K grid dimension."""
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _dequant_block(packed_ref, scales_ref, lut_ref, bits=bits,
                       group_size=group_size, bk=bk)

    # ---- MXU matmul, f32 accumulation -----------------------------------
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def _lut_matmul_int_kernel(x_ref, xs_ref, packed_ref, scales_ref, lut_ref,
                           o_ref, acc_ref, *, bits: int, group_size: int,
                           bk: int, n_k: int, abits: int, out_dtype):
    """Int-activation tile: x_ref carries ``abits``-bit signed codes.

    The codes are widened to f32 with Algorithm-1 typeconv (exact for
    abits-bit ints) and the per-token scale ``xs`` is applied once at the
    final store, so y == (x_q @ dequant(W)) * xs bit-for-bit with the ref.
    """
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _dequant_block(packed_ref, scales_ref, lut_ref, bits=bits,
                       group_size=group_size, bk=bk)

    xf = int_to_f32_compute(x_ref[...], n=abits)          # exact int -> f32
    acc_ref[...] += jnp.dot(xf, w, preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k - 1)
    def _store():
        o_ref[...] = (acc_ref[...] * xs_ref[...]).astype(out_dtype)


def vmem_bytes(bm: int, bn: int, bk: int, bits: int, group_size: int,
               int_act: bool = False) -> int:
    """VMEM one grid step of the kernel holds, in bytes.

    Double-buffered input blocks (activations, packed codes, group
    scales, the LUT row, and the per-token scales of the int path) and
    output block, the f32 accumulator, and what Mosaic materializes while
    decoding: one [bk, bn] f32 tile per 128-entry LUT half plus three
    more when the halves are merged by a select, and for int activations
    two [bm, bk] typeconv intermediates.  Compiled against a v5e under a
    shrinking limit, the kernel needs 60-80% of this at tinymistral's
    shapes (M 8 and 512, 4- and 8-bit).
    """
    groups = bk // group_size
    inputs = (bm * bk + groups * words_per_group(bits, group_size) * bn
              + groups * bn + _lut_lanes(bits) + (bm if int_act else 0)) * 4
    out = bm * bn * 4
    halves = _lut_lanes(bits) // 128
    tiles = 1 if halves == 1 else halves + 2
    return (2 * (inputs + out) + bm * bn * 4 + tiles * bk * bn * 4
            + (2 * bm * bk * 4 if int_act else 0))


def _vmem_limit(need: int) -> int:
    """Scoped-VMEM limit for a kernel that holds ``need`` bytes: a quarter
    more for Mosaic's internal scratch, and never below the compiler's
    16 MiB default."""
    return max(16 * 2**20, need * 5 // 4)


def _lut_row(codebook, bits: int):
    """The codebook as the kernel's LUT row [1, _lut_lanes(bits)]."""
    cb = codebook.astype(jnp.float32)
    return jnp.pad(cb, (0, _lut_lanes(bits) - cb.shape[0]))[None]


def _call(kernel, operands, act_specs, *, m, n, k, bits, group_size, bm, bn,
          bk, out_dtype, interpret):
    """pallas_call over the (M/bm, N/bn, K/bk) grid, K innermost."""
    if interpret is None:
        interpret = default_interpret()
    groups = bk // group_size
    pk_rows = groups * words_per_group(bits, group_size)
    need = vmem_bytes(bm, bn, bk, bits, group_size,
                      int_act=len(act_specs) > 1)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=act_specs + [
            pl.BlockSpec((pk_rows, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((groups, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, _lut_lanes(bits)), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(need)),
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit, static_argnames=(
    "bits", "group_size", "k", "bm", "bn", "bk", "out_dtype", "interpret"))
def lut_matmul_pallas(x, packed, scales, codebook, *, bits: int,
                      group_size: int, k: int, bm: int = 8, bn: int = 256,
                      bk: int = 512, out_dtype=jnp.float32,
                      interpret: Optional[bool] = None):
    """y[M, N] = x[M, K] @ dequant(packed, scales, codebook).

    All of M % bm, N % bn, K % bk, bk % group_size must be 0 (ops.py pads).
    ``interpret=None`` takes the platform's choice
    (``repro.kernels.default_interpret``): compiled on a TPU, interpreted
    elsewhere.
    """
    m, kx = x.shape
    assert kx == k, (kx, k)
    n = packed.shape[-1]
    kernel = functools.partial(
        _lut_matmul_kernel, bits=bits, group_size=group_size, bk=bk,
        n_k=k // bk, out_dtype=out_dtype)
    return _call(kernel, (x, packed, scales, _lut_row(codebook, bits)),
                 [pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))],
                 m=m, n=n, k=k, bits=bits, group_size=group_size, bm=bm,
                 bn=bn, bk=bk, out_dtype=out_dtype, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "bits", "group_size", "k", "abits", "bm", "bn", "bk", "out_dtype",
    "interpret"))
def lut_matmul_int_pallas(x_q, x_scale, packed, scales, codebook, *,
                          bits: int, group_size: int, k: int, abits: int,
                          bm: int = 8, bn: int = 256, bk: int = 512,
                          out_dtype=jnp.float32,
                          interpret: Optional[bool] = None):
    """y[M, N] = (x_q[M, K] @ dequant(...)) * x_scale[M, 1].

    x_q: int32 ``abits``-bit signed activation codes; x_scale: per-token
    f32 scales, both from ``quant.quantize_activations``.
    """
    m, kx = x_q.shape
    assert kx == k, (kx, k)
    n = packed.shape[-1]
    kernel = functools.partial(
        _lut_matmul_int_kernel, bits=bits, group_size=group_size, bk=bk,
        n_k=k // bk, abits=abits, out_dtype=out_dtype)
    return _call(kernel,
                 (x_q, x_scale, packed, scales, _lut_row(codebook, bits)),
                 [pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0))],
                 m=m, n=n, k=k, bits=bits, group_size=group_size, bm=bm,
                 bn=bn, bk=bk, out_dtype=out_dtype, interpret=interpret)
