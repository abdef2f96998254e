"""Pallas TPU kernel: one-token decode attention over the paged int8 KV pool.

The decode-phase hot loop the paper targets (Sec. III-B: the KV path maps
column-wise across C-SRAM arrays so the Q x K_cache^T product streams
without rebuilding LUTs).  On TPU the analogous structure is a paged
flash-decoding kernel that reads the pool where it lies:

  * the grid walks the batch's lanes; each lane's block table, position,
    the layer index and the layer's window arrive by scalar prefetch, and
    the int8 codes stay in HBM (``memory_space=ANY``); each lane's scale
    rows come gathered by its table (1/Dh of its codes' bytes);
  * a lane at position ``p`` reads only its live blocks, ``max(0, p -
    window + 1) // BS`` through ``p // BS``, a chunk of blocks (about 256
    positions) per loop step, double-buffered; a lane whose position is
    negative (inactive) reads nothing;
  * the int8 codes go to the MXU as they are (exact in bf16), and the
    per-position scales multiply scores and probabilities instead:
    ``q.(k_code k_scale) = k_scale (q.k_code)`` and ``sum p v_scale
    v_code``, so no dequantized K or V is ever written;
  * grouped-query heads: the pool row holds the KV heads side by side
    (heads-major, ``KV*Dh`` lanes), so the query is made block-diagonal,
    ``[H, KV*Dh]``, and one dot against the ``[P, KV*Dh]`` chunk scores
    every head; the ``[KV, BS]`` scale row of each block is spread to
    ``[H, P]`` by an exact one-hot dot;
  * online softmax (running max / sum) in f32 across the chunks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
CHUNK_POSITIONS = 256      # positions a lane reads per loop step


def live_blocks(position, window, block_size: int, xp=jnp):
    """(first, count) of the table columns a lane at ``position`` attends
    to under ``window``; count 0 for a negative (inactive) position.
    ``xp``: ``jnp`` inside the kernel, ``np`` for the host's count."""
    first = xp.maximum(position - window + 1, 0) // block_size
    count = xp.where(position >= 0, position // block_size - first + 1, 0)
    return first, count


def _kernel(meta_ref, pos_ref, tbl_ref,
            q_ref, expand_ref, offset_ref, ks_ref, vs_ref,
            k_hbm, v_hbm,
            o_ref,
            kbuf, vbuf, ksrows, vsrows, sem, m_ref, l_ref, acc_ref,
            *, bs: int, chunk: int, nbp: int, sm_scale: float):
    b = pl.program_id(0)
    layer, window = meta_ref[0], meta_ref[1]
    p = pos_ref[b]
    first, count = live_blocks(p, window, bs)
    n_chunks = (count + chunk - 1) // chunk
    npos = chunk * bs

    def column(i, j):
        """(live, table column) of block ``j`` of chunk ``i``; a block past
        the lane's last is not read (its positions are masked)."""
        col = first + i * chunk + j
        return col < first + count, jnp.minimum(col, nbp - 1)

    def copies(i, slot):
        out = []
        for j in range(chunk):
            live, col = column(i, j)
            blk = tbl_ref[b * nbp + col]
            out.append((live, [
                pltpu.make_async_copy(src.at[layer, blk], dst.at[slot, j],
                                      sem.at[slot])
                for src, dst in ((k_hbm, kbuf), (v_hbm, vbuf))]))
        return out

    def start(i, slot):
        for live, cps in copies(i, slot):
            @pl.when(live)
            def _():
                for cp in cps:
                    cp.start()

    def wait(i, slot):
        for live, cps in copies(i, slot):
            @pl.when(live)
            def _():
                for cp in cps:
                    cp.wait()

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_chunks > 0)
    def _():
        start(0, 0)

    q = q_ref[0]                                           # [H, KV*Dh] bf16
    expand = expand_ref[...]                               # [H, KV*BS]
    same_offset = offset_ref[...] > 0                      # [P, KV*BS]
    nt = (((1,), (1,)), ((), ()))

    def spread(rows):
        """The chunk's scales as [H, P]: row h, position (c, o) holds block
        c's scale of head h's KV head at offset o (an exact one-hot dot)."""
        rows = rows[...]                                   # [C, 1, KV*BS]
        width = rows.shape[-1]
        rows = jnp.broadcast_to(rows, (chunk, bs, width))
        rows = jnp.where(same_offset, rows.reshape(npos, width), 0.0)
        return jax.lax.dot_general(
            expand, rows, nt, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    def codes(buf, slot):
        c = buf[slot].astype(jnp.float32)                  # [C, BS, KV*Dh]
        return c.reshape(npos, c.shape[-1]).astype(jnp.bfloat16)

    def body(i, _):
        slot = i % 2

        @pl.when(i + 1 < n_chunks)
        def _():
            start(i + 1, 1 - slot)

        # the lane's table of scale rows sits in VMEM: pick the chunk's
        for j in range(chunk):
            _, col = column(i, j)
            ksrows[j] = ks_ref[0, pl.ds(col, 1), :]
            vsrows[j] = vs_ref[0, pl.ds(col, 1), :]
        wait(i, slot)
        pos = ((first + i * chunk) * bs
               + jax.lax.broadcasted_iota(jnp.int32, (1, npos), 1))
        valid = (pos <= p) & (pos > p - window)
        s = jax.lax.dot_general(q, codes(kbuf, slot), nt,
                                preferred_element_type=jnp.float32)
        s = jnp.where(valid, s * spread(ksrows) * sm_scale, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        prob = jnp.exp(s - m_new)                          # [H, P]
        l_ref[...] = alpha * l_ref[...] + jnp.sum(prob, axis=-1,
                                                  keepdims=True)
        pv = jnp.where(valid, prob * spread(vsrows), 0.0)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            pv.astype(jnp.bfloat16), codes(vbuf, slot),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return 0

    jax.lax.fori_loop(0, n_chunks, body, 0)
    o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _constants(n_heads: int, n_kv: int, bs: int, npos: int):
    """Head expansion [H, KV*BS] (row h selects its KV head's BS scale
    columns) and same-offset mask [P, KV*BS] (position (c, o) keeps the
    columns of offset o), as f32 0/1."""
    g = n_heads // n_kv
    col = np.arange(n_kv * bs)
    expand = (col[None, :] // bs == np.arange(n_heads)[:, None] // g)
    same = (col[None, :] % bs == np.arange(npos)[:, None] % bs)
    return (jnp.asarray(expand, jnp.float32), jnp.asarray(same, jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, k_scale, v_pool, v_scale, layer,
                           positions, block_tables, window, *,
                           interpret: bool = False):
    """Decode attention of one query token per lane over the paged pool.

    q: [B, H, Dh] (post-RoPE); k_pool/v_pool: int8 codes ``[L, NB, BS,
    KV*Dh]`` and k_scale/v_scale ``[L, NB, KV*BS]`` (heads-major, as
    ``lm.init_paged_cache`` lays them out, with this step's token already
    written); layer: this layer's index into the pool; positions: [B]
    int32, each lane's newest position (negative: an inactive lane, which
    reads nothing and returns zeros); block_tables: [B, nbp] int32;
    window: the layer's window (a traced scalar is fine).  Position ``t``
    of a lane at ``p`` is attended iff ``p - window < t <= p``.  Returns
    [B, H, Dh] f32.
    """
    b, h, dh = q.shape
    bs, row = k_pool.shape[2:]
    kv = row // dh
    g = h // kv
    nbp = block_tables.shape[1]
    chunk = max(1, min(nbp, CHUNK_POSITIONS // bs))   # blocks a loop step
    npos = chunk * bs
    # block-diagonal query: head h's Dh values at its KV head's lanes
    diag = jnp.eye(kv, dtype=bool)[None, :, None, :, None]
    qbd = jnp.where(diag, q.reshape(b, kv, g, 1, dh), 0.0)
    qbd = qbd.reshape(b, h, row).astype(jnp.bfloat16)
    expand, same = _constants(h, kv, bs, npos)
    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.asarray(window, jnp.int32)])
    kernel = functools.partial(_kernel, bs=bs, chunk=chunk, nbp=nbp,
                               sm_scale=1.0 / float(np.sqrt(dh)))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    full = lambda shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))
    # each lane's scale rows, gathered by table (a block's row is 1/Dh of
    # its codes' bytes): Mosaic cannot DMA a row narrower than 128 lanes
    # straight from the pool, as Mellum2's 4 heads x 16 are
    rows = lambda s: s[layer, block_tables]              # [B, nbp, KV*BS]
    lane_rows = pl.BlockSpec((1, nbp, kv * bs), lambda i, *_: (i, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, h, row), lambda i, *_: (i, 0, 0)),
                      full((h, kv * bs)), full((npos, kv * bs)),
                      lane_rows, lane_rows, any_spec, any_spec],
            out_specs=pl.BlockSpec((1, h, row), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, chunk, bs, row), k_pool.dtype),
                pltpu.VMEM((2, chunk, bs, row), v_pool.dtype),
                pltpu.VMEM((chunk, 1, kv * bs), jnp.float32),
                pltpu.VMEM((chunk, 1, kv * bs), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, row), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, h, row), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(meta, positions.astype(jnp.int32), block_tables.reshape(-1),
      qbd, expand, same, rows(k_scale), rows(v_scale), k_pool, v_pool)
    # head h's output sits at its own KV head's lanes
    out = out.reshape(b, kv, g, kv, dh)
    return jnp.sum(jnp.where(diag, out, 0.0), axis=3).reshape(b, h, dh)
