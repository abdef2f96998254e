"""Causal language model: init / train forward / prefill / decode.

Layer stack lowers as one ``jax.lax.scan`` over stacked per-layer params
(compile-time friendly at 512 devices); each scan body is rematerialized
when cfg.remat.  Serving supports SAIL-quantized weights (QTensor leaves)
and optionally int8-quantized ring-buffer KV caches.

VLM (phi-3-vision) rides on the same class: ``prefix_embeds`` (the stubbed
CLIP patch embeddings) are concatenated ahead of the token embeddings.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import blocks as blk
from repro.models.common import ModelConfig
from repro.models.layers import dense_init, norm_init, apply_norm, \
    sinusoidal_positions
from repro.models.sail_linear import mm


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def n_scan_blocks(cfg: ModelConfig) -> int:
    if cfg.family == "ssm":
        assert cfg.n_layers % cfg.slstm_every == 0
        return cfg.n_layers // cfg.slstm_every
    return cfg.n_layers


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    k_embed, k_blocks, k_head, k_pos = jax.random.split(key, 4)
    nb = n_scan_blocks(cfg)
    block_keys = jax.random.split(k_blocks, nb)
    blocks = jax.vmap(lambda k: blk.block_init(k, cfg))(block_keys)
    p = {
        "embed": dense_init(k_embed, (cfg.vocab, cfg.d_model)) * cfg.d_model ** 0.5,
        "blocks": blocks,
        "final_norm": norm_init(cfg),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(k_head, (cfg.d_model, cfg.vocab))
    if cfg.pos == "learned":
        p["pos_embed"] = dense_init(k_pos, (cfg.max_seq, cfg.d_model))
    return p


def _layer_slice(stacked, i):
    """Slice layer i out of scan-stacked params (handles QTensor leaves)."""
    return jax.tree_util.tree_map(lambda a: a[i], stacked)


# ---------------------------------------------------------------------------
# segmented layer stacks (mixed-precision serving)
# ---------------------------------------------------------------------------
#
# ``quantize_params`` with a per-layer bit allocation emits
# ``params["blocks"]`` as a LIST of scan-stacked trees (consecutive layers
# sharing one static precision each), because a single ``lax.scan`` can
# only carry one static ``bits``/``abits`` pair per stacked leaf — a
# segment is maximal in the JOINT (wbits, abits) assignment, so an
# activation-precision change cuts the stack exactly like a weight one.
# Inside a segment, every ``mm`` on a leaf carrying ``abits`` runs the
# *real* int-activation LUT-GEMV path (integer codes + per-token scale
# through the kernel), so the served datapath matches what the joint
# allocator priced.
# All model entry points below scan the segments back-to-back; a plain
# (non-list) blocks tree is the 1-segment case and lowers exactly as
# before.  Each segment traces and compiles its own scan body, so
# compile cost grows linearly with segment count — the allocator's
# ``max_segments`` cap (repro.core.sensitivity.enforce_max_segments)
# exists to bound it, and tests/test_joint_precision.py pins the
# scan-body-per-segment invariant.

def block_segments(params) -> list:
    """params["blocks"] as a list of stacked segment trees."""
    blocks = params["blocks"]
    if isinstance(blocks, (list, tuple)):
        return list(blocks)
    return [blocks]


def _segment_len(segment) -> int:
    """Number of layers in one stacked segment tree."""
    return jax.tree_util.tree_leaves(segment)[0].shape[0]


def _scan_segments(body_fn, x, segments):
    """Run ``lax.scan(body_fn, x, (seg, layer indices))`` over each
    segment in order, each layer's absolute index beside its params (a
    model whose layers differ in kind looks its window and RoPE up by
    it).  Returns (x, [per-segment stacked ys])."""
    ys = []
    offset = 0
    for seg in segments:
        n_seg = _segment_len(seg)
        x, y = jax.lax.scan(body_fn, x, (seg, _layer_ids(offset, n_seg)))
        ys.append(y)
        offset += n_seg
    return x, ys


def _layer_ids(offset: int, n: int) -> jax.Array:
    """Absolute indices of a segment's layers."""
    return jnp.arange(offset, offset + n, dtype=jnp.int32)


def _concat_segments(parts):
    """Concatenate per-segment stacked pytrees back to [L, ...] arrays."""
    if len(parts) == 1:
        return parts[0]
    return jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0), *parts)


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def embed_tokens(params, tokens, cfg: ModelConfig,
                 prefix_embeds: Optional[jax.Array] = None,
                 pos_offset: int = 0):
    x = jnp.take(params["embed"], tokens, axis=0)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    t = x.shape[1]
    if cfg.pos == "learned":
        x = x + jax.lax.dynamic_slice_in_dim(params["pos_embed"], pos_offset,
                                             t, 0)[None]
    elif cfg.pos == "sinusoidal":
        x = x + sinusoidal_positions(pos_offset + t, cfg.d_model)[pos_offset:][None]
    return x


def lm_logits(params, x, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return x @ params["embed"].T
    return mm(x, params["lm_head"])


# ---------------------------------------------------------------------------
# train / full-sequence forward
# ---------------------------------------------------------------------------

def forward(params, tokens, cfg: ModelConfig,
            prefix_embeds: Optional[jax.Array] = None,
            moe_mode: str = "dispatch"):
    """tokens [B, T] -> (logits [B, T(+P), V], aux_loss)."""
    x = embed_tokens(params, tokens, cfg, prefix_embeds)
    b, t, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))

    def body(carry, inp):
        x = carry
        p_l, layer = inp
        y, aux, _ = blk.block_apply_seq(p_l, x, cfg, positions,
                                        moe_mode=moe_mode, layer=layer)
        return y, aux

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, auxs = _scan_segments(body_fn, x, block_segments(params))
    x = apply_norm(params["final_norm"], x, cfg)
    aux = sum(jnp.sum(a) for a in auxs)
    return lm_logits(params, x, cfg), aux


def chunked_nll(x, head, targets, mask, chunk: int = 1024,
                transpose_head: bool = False):
    """Cross entropy without materializing full [B, T, V] logits.

    Computes per-T-chunk logits -> (sum nll, sum count), each chunk
    rematerialized so backward recomputes its logits instead of storing
    them (the vocab-sized f32 logits were the largest buffers in the
    dry-run memory analysis for V >= 32k).
    """
    b, t, d = x.shape
    if t % chunk or t <= chunk:
        return _nll_dense(x, head, targets, mask, transpose_head)
    n = t // chunk
    xc = jnp.moveaxis(x.reshape(b, n, chunk, d), 1, 0)
    tc = jnp.moveaxis(targets.reshape(b, n, chunk), 1, 0)
    mc = jnp.moveaxis(mask.reshape(b, n, chunk), 1, 0)

    @jax.checkpoint
    def one(carry, inp):
        s, c = carry
        xi, ti, mi = inp
        si, ci = _nll_dense(xi, head, ti, mi, transpose_head,
                            reduce_mean=False)
        return (s + si, c + ci), None

    (s, c), _ = jax.lax.scan(one, (jnp.zeros(()), jnp.zeros(())),
                             (xc, tc, mc))
    return s / jnp.maximum(c, 1.0)


def _nll_dense(x, head, targets, mask, transpose_head,
               reduce_mean: bool = True):
    logits = (x @ head.T if transpose_head else mm(x, head)).astype(
        jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    s = jnp.sum((logz - gold) * mask)
    c = jnp.sum(mask)
    if reduce_mean:
        return s / jnp.maximum(c, 1.0)
    return s, c


def loss_fn(params, batch, cfg: ModelConfig, moe_mode: str = "dispatch",
            aux_weight: float = 0.01):
    """Next-token cross entropy.  batch: {tokens [B, T+1]} (+prefix)."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = embed_tokens(params, inputs, cfg, batch.get("prefix_embeds"))
    b, t, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))

    def body(carry, inp):
        p_l, layer = inp
        y, aux, _ = blk.block_apply_seq(p_l, carry, cfg, positions,
                                        moe_mode=moe_mode, layer=layer)
        return y, aux

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, auxs = _scan_segments(body_fn, x, block_segments(params))
    x = apply_norm(params["final_norm"], x, cfg)
    npfx = x.shape[1] - targets.shape[1]
    if npfx:
        x = x[:, npfx:]
    mask = batch.get("mask", jnp.ones_like(targets, jnp.float32))
    if cfg.tie_embeddings:
        nll = chunked_nll(x, params["embed"], targets, mask,
                          transpose_head=True)
    else:
        nll = chunked_nll(x, params["lm_head"], targets, mask)
    aux = sum(jnp.sum(a) for a in auxs)
    return nll + aux_weight * aux, {"nll": nll, "aux": aux}


# ---------------------------------------------------------------------------
# serving: prefill + decode with ring-buffer KV cache
# ---------------------------------------------------------------------------

def init_cache(params, cfg: ModelConfig, batch: int, cache_len: int,
               quant_kv: bool = False) -> Dict[str, Any]:
    """Allocate the stacked per-layer decode cache.

    With ``batch = max_batch`` this is the serving engine's fixed slot
    pool: a ``[max_batch, cache_len]`` KV arena whose rows (slots) are
    independently written by ``prefill_into_slot`` and advanced by
    ``decode_step(..., active_mask=...)`` — requests come and go without
    the pool ever being reshaped or reallocated.
    """
    from repro.models import ssm as ssm_lib
    from repro.models import xlstm as xlstm_lib
    nb = n_scan_blocks(cfg)
    cache: Dict[str, Any] = {"length": jnp.zeros((batch,), jnp.int32)}
    if cfg.family == "ssm":
        layers: Dict[str, Any] = {}
        for i in range(cfg.slstm_every):
            if i == cfg.slstm_every - 1:
                st = xlstm_lib.init_slstm_state(cfg, batch)
                layers[f"slstm_{i}"] = jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a, (nb,) + a.shape), st)
            else:
                st = xlstm_lib.init_mlstm_state(cfg, batch)
                layers[f"mlstm_{i}"] = jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a, (nb,) + a.shape), st)
        cache["layers"] = layers
        return cache
    kv_shape = (nb, batch, cache_len, cfg.n_kv, cfg.head_dim)
    sc_shape = (nb, batch, cache_len, cfg.n_kv, 1)
    layers = {
        "k": jnp.zeros(kv_shape, jnp.int8 if quant_kv else jnp.float32),
        "v": jnp.zeros(kv_shape, jnp.int8 if quant_kv else jnp.float32),
    }
    if quant_kv:
        layers["k_scale"] = jnp.zeros(sc_shape, jnp.float32)
        layers["v_scale"] = jnp.zeros(sc_shape, jnp.float32)
    if cfg.family == "hybrid":
        st = ssm_lib.init_ssm_state(cfg, batch)
        layers["ssm"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (nb,) + a.shape), st)
    cache["layers"] = layers
    return cache


def init_paged_cache(params, cfg: ModelConfig, max_batch: int,
                     num_blocks: int, block_size: int,
                     quant_kv: bool = False) -> Dict[str, Any]:
    """Allocate a paged KV block pool.

    A flat pool of fixed-size blocks shared by every request; which block
    holds which request's tokens is decided per step by the
    ``block_tables`` argument of :func:`decode_step`.  The layout is
    lane-dense, heads-major in the last axis: ``k``/``v`` are ``[L,
    num_blocks, block_size, n_kv * head_dim]``, and with ``quant_kv`` the
    int8 codes' f32 scales are ``[L, num_blocks, n_kv * block_size]``.
    The decode step scatters into and gathers from this layout directly,
    so the pool rides its layer loop unchanged and is updated in place;
    attention reshapes each gathered view back to ``[.., n_kv,
    head_dim]`` (``blocks.paged_view``).  A ``[.., block_size, n_kv]``
    scale array would leave XLA's TPU layout an 8-wide minor axis, and
    the compiler then moves both whole scale pools through VMEM on every
    layer.  Callers conventionally reserve the LAST physical block as a
    trash block: retired lanes' table entries and masked scatter
    positions point at it so dead writes never land in a live block.
    ``length`` is still per-lane (``[max_batch]``).

    The serving engine donates the pool to every decode step
    (:func:`donating`): a caller holding an earlier ``engine.cache``
    must not read it after the next step.

    Attention families only — recurrent state (ssm/xlstm) is O(1) per lane
    and gains nothing from paging.
    """
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError(
            f"paged KV cache requires an attention family, got {cfg.family!r}")
    nb = n_scan_blocks(cfg)
    kv_shape = (nb, num_blocks, block_size, cfg.n_kv * cfg.head_dim)
    sc_shape = (nb, num_blocks, cfg.n_kv * block_size)
    layers: Dict[str, Any] = {
        "k": jnp.zeros(kv_shape, jnp.int8 if quant_kv else jnp.float32),
        "v": jnp.zeros(kv_shape, jnp.int8 if quant_kv else jnp.float32),
    }
    if quant_kv:
        layers["k_scale"] = jnp.zeros(sc_shape, jnp.float32)
        layers["v_scale"] = jnp.zeros(sc_shape, jnp.float32)
    return {"length": jnp.zeros((max_batch,), jnp.int32), "layers": layers}


def prefill(params, tokens, cfg: ModelConfig, cache_len: int,
            quant_kv: bool = False,
            prefix_embeds: Optional[jax.Array] = None,
            lengths: Optional[jax.Array] = None,
            moe_mode: str = "routed"):
    """Process the prompt, build the decode cache, return last logits.

    tokens: [B, T] (right-padded).  lengths: [B] true prompt lengths.
    """
    from repro.core.quant import quantize_kv
    b, t = tokens.shape
    if lengths is None:
        lengths = jnp.full((b,), t, jnp.int32)
    x = embed_tokens(params, tokens, cfg, prefix_embeds)
    tt = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(tt), (b, tt))

    def body(x, inp):
        p_l, layer = inp
        y, _, cache = blk.block_apply_seq(p_l, x, cfg, positions,
                                          moe_mode=moe_mode,
                                          collect_cache=True, layer=layer)
        return y, cache

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, cache_parts = _scan_segments(body_fn, x, block_segments(params))
    caches = _concat_segments(cache_parts)
    x = apply_norm(params["final_norm"], x, cfg)
    last = jnp.take_along_axis(
        x, (lengths - 1 + (tt - t))[:, None, None], axis=1)
    logits = lm_logits(params, last, cfg)[:, 0]

    # assemble ring cache from collected per-layer entries
    cache = init_cache(params, cfg, b, cache_len, quant_kv)
    cache["length"] = lengths + (tt - t)
    layers = dict(cache["layers"])
    if cfg.family == "ssm":
        for name, st in caches.items():
            layers[name] = st
    else:
        kv = caches["kv"]
        k_new, v_new = kv["k"], kv["v"]          # [L, B, T, KV, Dh]
        pad = cache_len - tt
        if pad >= 0:
            padkv = lambda a: jnp.pad(
                a, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            k_new, v_new = padkv(k_new), padkv(v_new)
        else:
            k_new = k_new[:, :, -cache_len:]
            v_new = v_new[:, :, -cache_len:]
        if quant_kv:
            kq, ks = quantize_kv(k_new)
            vq, vs = quantize_kv(v_new)
            layers.update(k=kq, v=vq, k_scale=ks, v_scale=vs)
        else:
            layers.update(k=k_new, v=v_new)
        if cfg.family == "hybrid":
            layers["ssm"] = caches["ssm"]
    cache["layers"] = layers
    return logits, cache


def _scatter_slots(pool: Dict[str, Any], fresh: Dict[str, Any],
                   slots: jax.Array) -> Dict[str, Any]:
    """Write a freshly prefilled batch-b cache into pool rows ``slots``.

    Every stacked per-layer array carries batch at axis 1 ([L, B, ...]);
    ``length`` carries it at axis 0 — a pure scatter, so the pool is never
    reshaped and untouched slots keep their contents bit-for-bit.
    """
    layers = jax.tree_util.tree_map(
        lambda dst, src: dst.at[:, slots].set(src.astype(dst.dtype)),
        pool["layers"], fresh["layers"])
    length = pool["length"].at[slots].set(fresh["length"])
    return {"length": length, "layers": layers}


_PREFILL_STATIC = ("cfg", "quant_kv", "moe_mode")


# One program per admission shape: prefill and its scatter compile
# together, cached on the shapes of ``tokens`` (batch, padded length);
# the donated pool is written in place, not copied.  Called eagerly, the
# layer scan would be retraced and recompiled at every admission.
@partial(jax.jit, static_argnames=_PREFILL_STATIC, donate_argnames="cache")
def prefill_into_slot(params, tokens, cache, slot, cfg: ModelConfig,
                      quant_kv: bool = False,
                      lengths: Optional[jax.Array] = None,
                      prefix_embeds: Optional[jax.Array] = None,
                      moe_mode: str = "routed"):
    """Prefill request(s) and write their KV into slots of an existing pool.

    tokens: [b, T] (right-padded prompts; typically b == 1 — one newly
    admitted request).  cache: the engine's ``[max_batch, cache_len]``
    pool from ``init_cache``, donated: the caller rebinds it to the
    returned pool.  slot: int or [b] int array of target rows.
    Returns (last-token logits [b, V], updated pool).  Handles the int8
    quant-KV path (codes + scales scattered together) and recurrent
    families (ssm/xlstm state rows are replaced the same way).
    """
    slots = jnp.atleast_1d(jnp.asarray(slot, jnp.int32))
    if cfg.family == "ssm":
        cache_len = 0
    else:
        cache_len = cache["layers"]["k"].shape[2]
    logits, fresh = prefill(params, tokens, cfg, cache_len=cache_len,
                            quant_kv=quant_kv, lengths=lengths,
                            prefix_embeds=prefix_embeds, moe_mode=moe_mode)
    return logits, _scatter_slots(cache, fresh, slots)


def _scatter_blocks(pool: Dict[str, Any], fresh: Dict[str, Any],
                    slots: jax.Array, phys: jax.Array,
                    offs: jax.Array) -> Dict[str, Any]:
    """Write a freshly prefilled batch-b cache into pool blocks.

    fresh layers are ``[L, b, T, n_kv, Dh(or 1)]``, written into the
    pool's lane-dense layout; ``phys``/``offs`` are flat ``[b*T]``
    (physical block, in-block offset) destinations for each of the b*T
    prefilled token rows.  Padding rows and rows that land in SHARED
    prefix blocks are redirected to the trash block by the caller, so
    shared blocks are never rewritten (sharers keep attending to
    bit-identical KV) and duplicate trash writes only ever carry dead
    values.
    """
    def put(dst, src):
        rows = src.reshape((src.shape[0], -1) + src.shape[3:])
        return blk.paged_write(dst, (slice(None),), phys, offs, rows)

    layers = jax.tree_util.tree_map(put, pool["layers"], fresh["layers"])
    length = pool["length"].at[slots].set(fresh["length"])
    return {"length": length, "layers": layers}


def _copy_blocks(layers: Dict[str, Any], src: jax.Array,
                 dst: jax.Array) -> Dict[str, Any]:
    """Copy-on-write: duplicate pool blocks ``src`` into free blocks ``dst``."""
    return jax.tree_util.tree_map(
        lambda a: a.at[:, dst].set(a[:, src]), layers)


_copy_blocks_jit = jax.jit(_copy_blocks, donate_argnums=(0,))


@partial(jax.jit, static_argnames=_PREFILL_STATIC, donate_argnames="cache")
def prefill_into_blocks(params, tokens, cache, slots, phys, offs,
                        cfg: ModelConfig, quant_kv: bool = False,
                        lengths: Optional[jax.Array] = None,
                        moe_mode: str = "routed"):
    """Prefill request(s) and scatter their KV into a paged block pool.

    tokens: [b, T] right-padded prompts.  cache: pool from
    :func:`init_paged_cache`, donated as in :func:`prefill_into_slot`.
    slots: [b] decode-lane indices (for ``length``).  phys/offs: flat
    [b*T] block destinations (trash-redirected where a row must not be
    written — padding and shared prefix blocks).
    Returns (last-token logits [b, V], updated pool).
    """
    slots = jnp.atleast_1d(jnp.asarray(slots, jnp.int32))
    logits, fresh = prefill(params, tokens, cfg, cache_len=tokens.shape[1],
                            quant_kv=quant_kv, lengths=lengths,
                            moe_mode=moe_mode)
    return logits, _scatter_blocks(
        cache, fresh, slots,
        jnp.asarray(phys, jnp.int32), jnp.asarray(offs, jnp.int32))


_DECODE_STATIC = ("cfg", "quant_kv", "moe_mode", "capture_layer_inputs",
                  "count_experts")


@partial(jax.jit, static_argnames=_DECODE_STATIC)
def decode_step(params, tokens, cache, cfg: ModelConfig,
                quant_kv: bool = False, moe_mode: str = "routed",
                active_mask: Optional[jax.Array] = None,
                capture_layer_inputs: bool = False,
                block_tables: Optional[jax.Array] = None,
                count_experts: bool = False):
    """One decode step.  tokens [B, 1] -> (logits [B, V], new cache).

    active_mask: optional [B] bool — retired slots keep their cache
    position frozen (their ``length`` does not advance) so the batch
    never reshapes as requests finish; their lanes still flow through
    the matmuls (the weight stream is shared either way) but their
    outputs are dead values the engine ignores until the slot is
    re-prefilled.

    block_tables: optional [B, max_blocks] int32 — paged mode.  cache is
    a pool from ``init_paged_cache``; lane i's logical block j lives in
    physical block ``block_tables[i, j]``.  Writes scatter through the
    table at ``position``; attention gathers the lane's blocks back into
    a contiguous [max_blocks*block_size] view.  Paged lanes must never
    wrap (callers enforce prompt+max_new <= max_blocks*block_size), under
    which the ring validity arithmetic reduces exactly to "slot <=
    position", so both layouts share one attention path.  Retired lanes'
    table rows point at the trash block.  The whole pool rides the layer
    scan's carry (the scan runs over each layer's params and index), so
    each layer writes its token and gathers its blocks in place instead
    of slicing its layer out and stacking it back; under donation
    (:func:`donating`) no pool-sized copy remains.  The ring pool keeps
    its per-layer scan inputs and outputs.

    capture_layer_inputs: additionally return each block's input
    activations as a third result ([n_layers, B, 1, D]) — the vectors
    the DFM's Pattern Reuse Table would see.  The serving engine feeds
    them to ``repro.planning.tap.ActivationTap`` so measured PRT
    discounts can recalibrate on live traffic.

    count_experts: additionally return, last, the routed MoE's tokens per
    expert over the active lanes, ``[n_layers, E]`` int32, computed in the
    same program (the engine's ``moe.*`` counters).

    A model whose layers differ in kind (``cfg.layer_types``) looks each
    layer's window and RoPE up by its absolute index in either layout.
    """
    b = tokens.shape[0]
    position = cache["length"]                   # absolute position of token
    x = embed_tokens(params, tokens, cfg, pos_offset=0)
    if cfg.pos == "learned":
        x = jnp.take(params["embed"], tokens, axis=0) + \
            params["pos_embed"][position][:, None]
    cache_len = (cache["layers"]["k"].shape[2]
                 if cfg.family != "ssm" else 0)
    if block_tables is not None:
        # paged: logical length = table width * block size (shape[2] is the
        # block size for a [L, NB, BS, n_kv * head_dim] pool)
        cache_len = block_tables.shape[1] * cache["layers"]["k"].shape[2]

    segments = block_segments(params)
    captured, routed = [], []
    if block_tables is not None:
        def paged_body(carry, inp):
            x, pool = carry
            p_l, layer = inp
            y, pool, tokens_l = blk.block_apply_decode(
                p_l, x, cfg, pool, position, cache_len,
                moe_mode=moe_mode, quant_kv=quant_kv,
                block_tables=block_tables, layer=layer, active=active_mask)
            return (y, pool), (x if capture_layer_inputs else None,
                               tokens_l if count_experts else None)

        pool = cache["layers"]
        offset = 0
        for seg in segments:
            n_seg = _segment_len(seg)
            (x, pool), (xs_seg, tok_seg) = jax.lax.scan(
                paged_body, (x, pool), (seg, _layer_ids(offset, n_seg)))
            captured.append(xs_seg)
            routed.append(tok_seg)
            offset += n_seg
        new_layers = pool
    else:
        def body(x, inp):
            p_l, cache_l, layer = inp
            y, new_cache_l, tokens_l = blk.block_apply_decode(
                p_l, x, cfg, cache_l, position, cache_len,
                moe_mode=moe_mode, quant_kv=quant_kv, layer=layer,
                active=active_mask)
            return y, (new_cache_l, x if capture_layer_inputs else None,
                       tokens_l if count_experts else None)

        new_parts = []
        offset = 0
        for seg in segments:
            n_seg = _segment_len(seg)
            cache_seg = jax.tree_util.tree_map(
                lambda a: a[offset:offset + n_seg], cache["layers"])
            x, (new_seg, xs_seg, tok_seg) = jax.lax.scan(
                body, x, (seg, cache_seg, _layer_ids(offset, n_seg)))
            captured.append(xs_seg)
            routed.append(tok_seg)
            new_parts.append(new_seg)
            offset += n_seg
        new_layers = _concat_segments(new_parts)
    x = apply_norm(params["final_norm"], x, cfg)
    logits = lm_logits(params, x, cfg)[:, 0]
    if active_mask is None:
        new_length = cache["length"] + 1
    else:
        new_length = cache["length"] + active_mask.astype(jnp.int32)
    new_cache = {"length": new_length, "layers": new_layers}
    out = (logits, new_cache)
    if capture_layer_inputs:
        out += (jnp.concatenate(captured, axis=0),)
    if count_experts:
        out += (jnp.concatenate(routed, axis=0),)
    return out


@lru_cache(maxsize=None)
def donating(step):
    """``step`` (:func:`decode_step`) compiled with its ``cache`` donated.

    For callers that rebind their pool after every call (the serving
    engine): XLA then writes the new KV into the old pool's buffers, and
    the arrays passed in are deleted.  ``decode_step`` itself leaves its
    input readable, for callers that reuse a cache (``core.sensitivity``).
    """
    return jax.jit(step, static_argnames=_DECODE_STATIC,
                   donate_argnames="cache")


# ---------------------------------------------------------------------------
# speculative decoding: fused k-step draft + multi-token verify
# ---------------------------------------------------------------------------

# Stream salts keep speculative RNG draws (draft sampling, acceptance
# coin flips, residual resampling, bonus draws) on distinct key streams
# from the engine's committed-token sampler, all derived from the same
# (seed, uid, per-request sample index) triple so results are invariant
# to pool layout and preemption.
DRAFT_SALT = 0x0D_0A_F7
ACCEPT_SALT = 0x0A_CC_E7
RESAMPLE_SALT = 0x0E_55_1D
BONUS_SALT = 0x0B_00_05


@partial(jax.jit, static_argnames=("cfg", "quant_kv", "moe_mode"))
def verify_step(params, tokens, cache, cfg: ModelConfig,
                quant_kv: bool = False, moe_mode: str = "routed",
                active_mask: Optional[jax.Array] = None,
                block_tables: Optional[jax.Array] = None):
    """Multi-token decode for speculative verification.

    tokens [B, T] occupy absolute positions ``cache["length"] + t``.
    Returns ``(logits [B, T, V], new cache)``: row i is the next-token
    distribution after consuming ``tokens[:, :i+1]`` on top of the
    cache, and KV is written (conservative precision) for all T
    positions — overwriting whatever the draft pass left there.
    ``length`` advances by T for active lanes; the speculative driver
    resets it to the accepted frontier afterwards, which is the whole
    rollback for the ring layout (stale slots beyond the frontier have
    ``held < 0`` until they are rewritten in order).

    Not valid for ``cfg.pos == "sinusoidal"`` — like ``decode_step``
    this embeds with ``pos_offset=0``, but here T > 1 rows would get
    positions 0..T-1 instead of a constant; the engine gates that off.
    """
    b, t = tokens.shape
    position = cache["length"]
    x = embed_tokens(params, tokens, cfg, pos_offset=0)
    if cfg.pos == "learned":
        qpos = position[:, None] + jnp.arange(t)[None, :]
        x = jnp.take(params["embed"], tokens, axis=0) + \
            params["pos_embed"][qpos]
    cache_len = cache["layers"]["k"].shape[2]
    if block_tables is not None:
        cache_len = block_tables.shape[1] * cache["layers"]["k"].shape[2]

    def body(x, inp):
        p_l, cache_l, layer = inp
        y, new_cache_l = blk.block_apply_verify(
            p_l, x, cfg, cache_l, position, cache_len,
            moe_mode=moe_mode, quant_kv=quant_kv,
            block_tables=block_tables, layer=layer)
        return y, new_cache_l

    segments = block_segments(params)
    new_parts = []
    offset = 0
    for seg in segments:
        n_seg = _segment_len(seg)
        cache_seg = jax.tree_util.tree_map(
            lambda a: a[offset:offset + n_seg], cache["layers"])
        x, new_seg = jax.lax.scan(body, x, (seg, cache_seg,
                                            _layer_ids(offset, n_seg)))
        new_parts.append(new_seg)
        offset += n_seg
    new_layers = _concat_segments(new_parts)
    x = apply_norm(params["final_norm"], x, cfg)
    logits = lm_logits(params, x, cfg)                   # [B, T, V]
    if active_mask is None:
        new_length = cache["length"] + t
    else:
        new_length = cache["length"] + t * active_mask.astype(jnp.int32)
    return logits, {"length": new_length, "layers": new_layers}


@partial(jax.jit, static_argnames=("cfg", "k", "quant_kv", "moe_mode",
                                   "temperature", "seed"))
def draft_tokens(params, tokens, cache, cfg: ModelConfig, k: int,
                 quant_kv: bool = False, moe_mode: str = "routed",
                 active_mask: Optional[jax.Array] = None,
                 block_tables: Optional[jax.Array] = None,
                 temperature: float = 0.0, seed: int = 0,
                 uids: Optional[jax.Array] = None,
                 indices: Optional[jax.Array] = None):
    """Draft ``k`` tokens per lane in ONE jitted dispatch.

    Python-unrolls k single-token decode steps (under the *draft* weight
    tree) into a single program, sampling between steps: argmax at
    ``temperature == 0``, else categorical with per-row keys
    ``fold_in(fold_in(fold_in(PRNGKey(seed), uid), index + i),
    DRAFT_SALT)`` so draft draws never collide with the committed-token
    sampler's stream.  This is where the speculative speedup comes from
    on the host backend: one dispatch (plus one verify dispatch) per
    ~E[accepted]+1 tokens instead of one per token.

    tokens: [B, 1] — the pending (committed-but-unfed) token.  Returns
    ``(draft [B, k] int32, draft_logits [B, k, V], new cache)``.  Draft
    KV lands at positions ``length .. length+k-1`` at draft precision;
    the verify pass overwrites every one of those slots, so nothing
    drafted ever survives in the cache.
    """
    drafted = []
    qlogits = []
    tok = tokens
    for i in range(k):
        logits, cache = decode_step(
            params, tok, cache, cfg, quant_kv=quant_kv, moe_mode=moe_mode,
            active_mask=active_mask, block_tables=block_tables)
        if temperature <= 0.0:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            base = jax.random.PRNGKey(seed)

            def draw(uid, idx, row):
                key = jax.random.fold_in(
                    jax.random.fold_in(jax.random.fold_in(base, uid), idx),
                    DRAFT_SALT)
                return jax.random.categorical(key, row / temperature)

            nxt = jax.vmap(draw)(uids, indices + i, logits).astype(jnp.int32)
        drafted.append(nxt)
        qlogits.append(logits)
        tok = nxt[:, None]
    return (jnp.stack(drafted, axis=1), jnp.stack(qlogits, axis=1), cache)


def greedy_generate(params, prompt, cfg: ModelConfig, max_new: int,
                    cache_len: Optional[int] = None,
                    quant_kv: bool = False):
    """Reference generation loop (serving engine uses its own)."""
    b, t = prompt.shape
    cache_len = cache_len or (t + max_new)
    if cfg.lane_window is not None:
        cache_len = min(cache_len, cfg.lane_window)
    logits, cache = prefill(params, prompt, cfg, cache_len, quant_kv)
    out = []
    tok = jnp.argmax(logits, axis=-1)[:, None]
    for _ in range(max_new):
        out.append(tok)
        logits, cache = decode_step(params, tok, cache, cfg, quant_kv)
        tok = jnp.argmax(logits, axis=-1)[:, None]
    return jnp.concatenate(out, axis=1)
