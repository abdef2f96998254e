"""Decoder blocks for every architecture family, built scan-compatible:
all layers of an arch share one pytree structure so the layer stack lowers
as a single ``jax.lax.scan`` body (fast compile at 512 devices).

xLSTM's heterogeneous stack (one sLSTM per ``slstm_every`` mLSTMs) is
handled by scanning over homogeneous *super-blocks* of ``slstm_every``
layers (unrolled inside the scan body).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.models.common import ModelConfig
from repro.models.layers import (apply_attention, apply_mlp, apply_norm,
                                 attention_init, layer_attention, mlp_init,
                                 norm_init)
from repro.kernels.decode_attn.kernel import paged_decode_attention
from repro.models.sail_linear import mm
from repro.dist.sharding import maybe_constrain, tp_active, tp_all_reduce


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def block_init(key, cfg: ModelConfig):
    """One layer's params (stacked by the caller via vmap over keys)."""
    ks = jax.random.split(key, 8)
    if cfg.family == "ssm":  # xlstm super-block
        n_in = cfg.slstm_every
        sub_ks = jax.random.split(ks[0], n_in)
        subs = []
        for i in range(n_in):
            kk = jax.random.split(sub_ks[i], 2)
            if i == n_in - 1:  # last of the super-block is sLSTM
                subs.append({"norm": norm_init(cfg),
                             "slstm": xlstm_lib.slstm_init(kk[0], cfg)})
            else:
                subs.append({"norm": norm_init(cfg),
                             "mlstm": xlstm_lib.mlstm_init(kk[0], cfg)})
        return {"subs": subs}

    p: Dict[str, Any] = {
        "attn_norm": norm_init(cfg),
        "attn": attention_init(ks[0], cfg),
        "mlp_norm": norm_init(cfg),
    }
    if cfg.family == "moe":
        p["moe"] = moe_lib.moe_init(ks[1], cfg)
    else:
        p["mlp"] = mlp_init(ks[1], cfg)
    if cfg.family == "hybrid":
        p["ssm_norm"] = norm_init(cfg)
        p["ssm"] = ssm_lib.ssm_init(ks[2], cfg)
    return p


# ---------------------------------------------------------------------------
# forward (train / prefill: full sequence)
# ---------------------------------------------------------------------------

def block_apply_seq(p, x, cfg: ModelConfig, positions,
                    moe_mode: str = "dispatch",
                    collect_cache: bool = False, layer=None):
    """Full-sequence block.  Returns (x, aux_loss, cache_entries).

    ``layer``: this layer's absolute index, which picks its window and
    RoPE where ``cfg.layer_types`` mixes kinds.  The output dtype always
    matches the input dtype (scan-carry stable under bf16 mixed
    precision)."""
    in_dtype = x.dtype
    x = maybe_constrain(x, "batch", None, None)
    aux = jnp.zeros((), jnp.float32)
    cache = {}

    if cfg.family == "ssm":
        for i, sub in enumerate(p["subs"]):
            h = apply_norm(sub["norm"], x, cfg)
            if "slstm" in sub:
                if collect_cache:
                    y, st = xlstm_lib.apply_slstm(sub["slstm"], h, cfg,
                                                  return_state=True)
                    cache[f"slstm_{i}"] = st
                else:
                    y = xlstm_lib.apply_slstm(sub["slstm"], h, cfg)
            else:
                if collect_cache:
                    y, st = xlstm_lib.apply_mlstm(sub["mlstm"], h, cfg,
                                                  return_state=True)
                    cache[f"mlstm_{i}"] = st
                else:
                    y = xlstm_lib.apply_mlstm(sub["mlstm"], h, cfg)
            x = (x + y).astype(in_dtype)
        return x, aux, cache

    # --- attention (+ parallel mamba for hybrid) -------------------------
    h = apply_norm(p["attn_norm"], x, cfg)
    window, rope = layer_attention(cfg, layer)
    attn_out = apply_attention(p["attn"], h, cfg, positions=positions,
                               causal=True,
                               window=cfg.window if rope is None else window,
                               rope=rope)
    if collect_cache:
        cache["kv"] = _kv_from_seq(p["attn"], h, cfg, positions, rope)
    if cfg.family == "hybrid":
        hs = apply_norm(p["ssm_norm"], x, cfg)
        if collect_cache:
            ssm_out, st = ssm_lib.apply_ssm(p["ssm"], hs, cfg,
                                            return_state=True)
            cache["ssm"] = st
        else:
            ssm_out = ssm_lib.apply_ssm(p["ssm"], hs, cfg)
        x = (x + 0.5 * (attn_out + ssm_out)).astype(in_dtype)
    else:
        x = (x + attn_out).astype(in_dtype)

    # --- mlp / moe --------------------------------------------------------
    h = apply_norm(p["mlp_norm"], x, cfg)
    if cfg.family == "moe":
        y, aux = moe_lib.apply_moe(p["moe"], h, cfg, mode=moe_mode)
    else:
        y = apply_mlp(p["mlp"], h, cfg)
    x = (x + y).astype(in_dtype)
    return x, aux, cache


def _kv_from_seq(attn_p, h, cfg: ModelConfig, positions, rope=None):
    """Recompute K/V for the prefill cache (keys stored post-RoPE)."""
    from repro.models.layers import apply_rope, _qk_norm
    b, t, _ = h.shape
    k = mm(h, attn_p["wk"]).reshape(b, t, cfg.n_kv, cfg.head_dim)
    v = mm(h, attn_p["wv"]).reshape(b, t, cfg.n_kv, cfg.head_dim)
    if cfg.qk_norm:
        k = _qk_norm(k, attn_p["k_norm"]["scale"], cfg.norm_eps)
    if cfg.pos == "rope":
        k = apply_rope(k, positions, cfg, rope)
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# decode (single token, cache update)
# ---------------------------------------------------------------------------

def block_apply_decode(p, x, cfg: ModelConfig, layer_cache, position,
                       cache_len: int, moe_mode: str = "routed",
                       quant_kv: bool = False, block_tables=None,
                       layer=None, active=None):
    """One-token decode.  x: [B, 1, D]; position: [B] absolute positions.

    layer_cache holds this layer's state (ring-buffered KV of size
    ``cache_len``, ssm/xlstm states).  Returns (x, new_cache, routed):
    ``routed`` is the routed MoE's tokens per expert [E] over the
    ``active`` lanes ([B] bool; all where None), None for other layers.
    ``layer`` (this layer's absolute index) picks the window and RoPE
    where ``cfg.layer_types`` mixes kinds.

    block_tables: optional [B, max_blocks] int32 — paged mode, where
    layer_cache is the WHOLE block pool of every layer (lane-dense
    ``[L, num_blocks, block_size, KV*Dh]``, scales ``[L, num_blocks,
    KV*block_size]``; ``lm.init_paged_cache``), ``layer`` is this
    layer's index into it and ``cache_len == max_blocks * block_size``.
    The write scatters one token per lane at ``(layer, block, offset)``;
    attention then reads the lane's blocks of this layer: with an int8
    pool through :func:`_paged_int8_attend` (on a TPU, a kernel that
    reads only the live blocks in place), otherwise by gathering them
    into a contiguous view that reuses the ring validity math (paged
    lanes never wrap, so "slot holds position slot" makes the two
    formulas agree).  ``active`` lanes ([B] bool) are the ones the kernel
    reads for.  The pool goes in and out whole, so a caller that carries
    it through its layer loop updates it in place.
    """
    from repro.core.quant import quantize_kv
    from repro.models.layers import apply_rope, _qk_norm
    new_cache = dict(layer_cache)
    in_dtype = x.dtype
    b = x.shape[0]

    if cfg.family == "ssm":
        for i, sub in enumerate(p["subs"]):
            h = apply_norm(sub["norm"], x, cfg)
            if "slstm" in sub:
                y, st = xlstm_lib.apply_slstm(
                    sub["slstm"], h, cfg, state=layer_cache[f"slstm_{i}"],
                    return_state=True)
                new_cache[f"slstm_{i}"] = st
            else:
                y, st = xlstm_lib.apply_mlstm(
                    sub["mlstm"], h, cfg, state=layer_cache[f"mlstm_{i}"],
                    return_state=True)
                new_cache[f"mlstm_{i}"] = st
            x = (x + y).astype(in_dtype)
        return x, new_cache, None

    window, rope = layer_attention(cfg, layer)
    h = apply_norm(p["attn_norm"], x, cfg)
    q = mm(h, p["attn"]["wq"]).reshape(b, 1, cfg.n_heads, cfg.head_dim)
    k = mm(h, p["attn"]["wk"]).reshape(b, 1, cfg.n_kv, cfg.head_dim)
    v = mm(h, p["attn"]["wv"]).reshape(b, 1, cfg.n_kv, cfg.head_dim)
    if cfg.qk_norm:
        q = _qk_norm(q, p["attn"]["q_norm"]["scale"], cfg.norm_eps)
        k = _qk_norm(k, p["attn"]["k_norm"]["scale"], cfg.norm_eps)
    if cfg.pos == "rope":
        q = apply_rope(q, position[:, None], cfg, rope)
        k = apply_rope(k, position[:, None], cfg, rope)

    # the KV write and attention over the cache: one ``attn`` scope in
    # the trace's op names
    with jax.named_scope("attn"):
        if block_tables is not None:
            # paged write: scatter through the block table, then attend
            # over the lane's blocks
            bs = layer_cache["k"].shape[2]
            nbp = block_tables.shape[1]
            logical = jnp.clip(position // bs, 0, nbp - 1)
            off = position % bs
            phys = jnp.take_along_axis(block_tables, logical[:, None],
                                       axis=1)[:, 0]
            write = lambda name, val: paged_write(
                layer_cache[name], (layer,), phys, off, val[:, 0],
                one_per_block=True)
            if quant_kv:
                kq, ks = quantize_kv(k)
                vq, vs = quantize_kv(v)
                kc, vc = write("k", kq), write("v", vq)
                ksc, vsc = write("k_scale", ks), write("v_scale", vs)
                new_cache.update(k=kc, v=vc, k_scale=ksc, v_scale=vsc)
                attn_out = _paged_int8_attend(
                    q, (kc, ksc, vc, vsc), layer, position, block_tables,
                    active, cfg, cache_len, window)
            else:
                kc, vc = write("k", k), write("v", v)
                new_cache.update(k=kc, v=vc)
                gather = lambda arr: paged_view(arr[layer, block_tables],
                                                cfg)
                attn_out = _decode_attend(q, gather(kc), gather(vc),
                                          position, cfg, cache_len, window)
        else:
            # ring-buffer write at position % cache_len
            slot = (position % cache_len)[:, None, None, None]
            if quant_kv:
                kq, ks = quantize_kv(k)
                vq, vs = quantize_kv(v)
                kc = _ring_write(layer_cache["k"], kq, slot)
                vc = _ring_write(layer_cache["v"], vq, slot)
                ksc = _ring_write(layer_cache["k_scale"], ks, slot)
                vsc = _ring_write(layer_cache["v_scale"], vs, slot)
                new_cache.update(k=kc, v=vc, k_scale=ksc, v_scale=vsc)
                kf = kc.astype(jnp.float32) * ksc
                vf = vc.astype(jnp.float32) * vsc
            else:
                kc = _ring_write(layer_cache["k"], k, slot)
                vc = _ring_write(layer_cache["v"], v, slot)
                new_cache.update(k=kc, v=vc)
                kf, vf = kc, vc
            attn_out = _decode_attend(q, kf, vf, position, cfg, cache_len,
                                      window)
    attn_out = tp_all_reduce(
        mm(attn_out.reshape(b, 1, cfg.q_dim), p["attn"]["wo"]))

    if cfg.family == "hybrid":
        hs = apply_norm(p["ssm_norm"], x, cfg)
        ssm_out, st = ssm_lib.apply_ssm(p["ssm"], hs, cfg,
                                        state=layer_cache["ssm"],
                                        return_state=True)
        new_cache["ssm"] = st
        x = (x + 0.5 * (attn_out + ssm_out)).astype(in_dtype)
    else:
        x = (x + attn_out).astype(in_dtype)

    h = apply_norm(p["mlp_norm"], x, cfg)
    routed = None
    if cfg.family == "moe" and moe_mode == "routed":
        y, routed = moe_lib.apply_moe_routed(p["moe"], h, cfg, valid=active)
    elif cfg.family == "moe":
        y, _ = moe_lib.apply_moe(p["moe"], h, cfg, mode=moe_mode)
    else:
        y = apply_mlp(p["mlp"], h, cfg)
    return (x + y).astype(in_dtype), new_cache, routed


def _paged_int8_attend(q, pool, layer, position, block_tables, active,
                       cfg: ModelConfig, cache_len: int, window=None):
    """Decode attention over the paged int8 pool, this step's token
    already written; pool: (k codes, k scales, v codes, v scales) of
    every layer.

    On a TPU the Pallas kernel reads each active lane's live blocks in
    place (``kernels.decode_attn``); elsewhere, and under tensor
    parallelism, each lane's whole table is gathered, dequantized and
    attended by :func:`_decode_attend`, the oracle the kernel is tested
    against.  ``lax.platform_dependent`` picks the branch when the step
    is lowered, so the compiled program holds only one of them."""
    def gathered(q, kc, ksc, vc, vsc):
        # one gather indexed by (layer, table): pool[layer][table] would
        # slice the whole layer out of the pool first
        view = lambda arr: paged_view(arr[layer, block_tables], cfg)
        kf = view(kc).astype(jnp.float32) * view(ksc)
        vf = view(vc).astype(jnp.float32) * view(vsc)
        return _decode_attend(q, kf, vf, position, cfg, cache_len, window)

    if tp_active():
        return gathered(q, *pool)

    def in_place(q, kc, ksc, vc, vsc):
        w = window
        if w is None:
            w = cfg.window if cfg.window is not None else cache_len
        lanes = position if active is None else jnp.where(active, position,
                                                          -1)
        out = paged_decode_attention(q[:, 0], kc, ksc, vc, vsc, layer,
                                     lanes, block_tables, w)
        return out[:, None].astype(q.dtype)

    return jax.lax.platform_dependent(q, *pool, tpu=in_place,
                                      default=gathered)


def _ring_write(cache, val, slot):
    """Scatter one token into the ring cache (in-place under donation).

    cache [B, S, KV, D(or 1)], val [B, 1, KV, D], slot [B,1,1,1].
    A batched dynamic-update (scatter) touches only the written slot —
    bytes ~ O(B*KV*D), not O(B*S*KV*D) like a one-hot masked rewrite.
    """
    b = cache.shape[0]
    idx = slot.reshape(b)
    return cache.at[jnp.arange(b), idx].set(
        val[:, 0].astype(cache.dtype), unique_indices=True,
        indices_are_sorted=False)


def paged_write(pool, lead, phys, off, val, one_per_block: bool = False):
    """Scatter token rows into a lane-dense paged pool.

    pool: KV codes ``[.., NB, BS, KV*D]`` or their scales ``[.., NB,
    KV*BS]`` (both heads-major in the last axis); ``lead`` indexes the
    leading layer axis (``(layer,)``, ``(slice(None),)`` for every layer,
    ``()`` for one layer's pool); phys/off [N]: each row's block and
    in-block offset; val [.., N, KV, D] (scales [.., N, KV, 1]).  No
    ``unique_indices``: retired lanes share the trash block, so duplicate
    destinations are expected — their values are dead either way (the
    engine never reads the trash block through a live table).

    ``one_per_block`` (decode: no live block takes two tokens in one
    call): scales are written as whole block rows, each read, given its
    token's ``KV`` columns and written back.  An element scatter into the
    float32 scale pool leads the TPU compiler to stage the whole pool
    through on-chip memory around it, on every layer, once attention
    leaves that memory free.
    """
    kv = val.shape[-2]
    if pool.ndim - len(lead) == 3:                   # codes
        rows = val.reshape(val.shape[:-2] + pool.shape[-1:])
        return pool.at[lead + (phys, off)].set(rows.astype(pool.dtype))
    bs = pool.shape[-1] // kv                        # scales
    if one_per_block:
        rows = pool[lead + (phys,)]                  # [.., N, KV*BS]
        mine = jnp.arange(pool.shape[-1]) % bs == off[:, None]
        new = jnp.repeat(val[..., 0], bs, axis=-1).astype(pool.dtype)
        return pool.at[lead + (phys,)].set(jnp.where(mine, new, rows))
    cols = off[:, None] + bs * jnp.arange(kv)
    return pool.at[lead + (phys[:, None], cols)].set(
        val[..., 0].astype(pool.dtype))


def paged_view(blocks, cfg: ModelConfig):
    """A lane's gathered blocks as one contiguous [B, S, KV, D] view.

    blocks: codes [B, nbp, BS, KV*D] -> [B, nbp*BS, KV, D]; scales
    [B, nbp, KV*BS] -> [B, nbp*BS, KV, 1]."""
    b, nbp = blocks.shape[:2]
    if blocks.ndim == 4:
        return blocks.reshape(b, nbp * blocks.shape[2], cfg.n_kv,
                              cfg.head_dim)
    heads = blocks.reshape(b, nbp, cfg.n_kv, -1)     # [B, nbp, KV, BS]
    return jnp.swapaxes(heads, 2, 3).reshape(b, -1, cfg.n_kv, 1)


# ---------------------------------------------------------------------------
# speculative verify (multi-token decode, cache update)
# ---------------------------------------------------------------------------

def block_apply_verify(p, x, cfg: ModelConfig, layer_cache, position,
                       cache_len: int, moe_mode: str = "routed",
                       quant_kv: bool = False, block_tables=None,
                       layer=None):
    """Multi-token decode for speculative verification.

    x: [B, T, D] — the pending token plus the drafted tokens, occupying
    absolute positions ``position + t`` (position: [B] is the first
    slot's position).  Writes KV for all T positions — overwriting any
    draft-precision KV the draft pass left at the same slots — and
    attends chunk-causally: query t sees every cached position ``<=
    position + t`` inside the window, including the tokens written this
    call, never the ones after it.

    Attention families only: recurrent state (ssm/hybrid) cannot be
    rolled back to an accepted frontier, so the engine gates speculative
    decoding off for those families.
    """
    from repro.core.quant import quantize_kv
    from repro.models.layers import apply_rope, _qk_norm
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError(
            f"speculative verify requires a pure-attention family, "
            f"got {cfg.family!r}")
    new_cache = dict(layer_cache)
    in_dtype = x.dtype
    b, t, _ = x.shape

    h = apply_norm(p["attn_norm"], x, cfg)
    q = mm(h, p["attn"]["wq"]).reshape(b, t, cfg.n_heads, cfg.head_dim)
    k = mm(h, p["attn"]["wk"]).reshape(b, t, cfg.n_kv, cfg.head_dim)
    v = mm(h, p["attn"]["wv"]).reshape(b, t, cfg.n_kv, cfg.head_dim)
    if cfg.qk_norm:
        q = _qk_norm(q, p["attn"]["q_norm"]["scale"], cfg.norm_eps)
        k = _qk_norm(k, p["attn"]["k_norm"]["scale"], cfg.norm_eps)
    qpos = position[:, None] + jnp.arange(t)[None, :]    # [B, T] absolute
    window, rope = layer_attention(cfg, layer)
    if cfg.pos == "rope":
        q = apply_rope(q, qpos, cfg, rope)
        k = apply_rope(k, qpos, cfg, rope)

    if block_tables is not None:
        bs = layer_cache["k"].shape[1]
        nbp = block_tables.shape[1]
        logical = jnp.clip(qpos // bs, 0, nbp - 1)       # [B, T]
        off = qpos % bs
        phys = jnp.take_along_axis(block_tables, logical, axis=1)
        # T tokens per lane, flattened to B*T rows; masked lanes' tables
        # point at the trash block
        write = lambda name, val: paged_write(
            layer_cache[name], (), phys.reshape(-1), off.reshape(-1),
            val.reshape((b * t,) + val.shape[2:]))
        gather = lambda arr: paged_view(arr[block_tables], cfg)
        if quant_kv:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            kc, vc = write("k", kq), write("v", vq)
            ksc, vsc = write("k_scale", ks), write("v_scale", vs)
            new_cache.update(k=kc, v=vc, k_scale=ksc, v_scale=vsc)
            kf = gather(kc).astype(jnp.float32) * gather(ksc)
            vf = gather(vc).astype(jnp.float32) * gather(vsc)
        else:
            kc, vc = write("k", k), write("v", v)
            new_cache.update(k=kc, v=vc)
            kf, vf = gather(kc), gather(vc)
    else:
        slot = qpos % cache_len                          # [B, T]
        if quant_kv:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            kc = _ring_write_multi(layer_cache["k"], kq, slot)
            vc = _ring_write_multi(layer_cache["v"], vq, slot)
            ksc = _ring_write_multi(layer_cache["k_scale"], ks, slot)
            vsc = _ring_write_multi(layer_cache["v_scale"], vs, slot)
            new_cache.update(k=kc, v=vc, k_scale=ksc, v_scale=vsc)
            kf = kc.astype(jnp.float32) * ksc
            vf = vc.astype(jnp.float32) * vsc
        else:
            kc = _ring_write_multi(layer_cache["k"], k, slot)
            vc = _ring_write_multi(layer_cache["v"], v, slot)
            new_cache.update(k=kc, v=vc)
            kf, vf = kc, vc

    attn_out = _verify_attend(q, kf, vf, position, cfg, cache_len, window)
    attn_out = tp_all_reduce(
        mm(attn_out.reshape(b, t, cfg.q_dim), p["attn"]["wo"]))
    x = (x + attn_out).astype(in_dtype)

    h = apply_norm(p["mlp_norm"], x, cfg)
    if cfg.family == "moe":
        y, _ = moe_lib.apply_moe(p["moe"], h, cfg, mode=moe_mode)
    else:
        y = apply_mlp(p["mlp"], h, cfg)
    return (x + y).astype(in_dtype), new_cache


def _ring_write_multi(cache, val, slot):
    """Scatter T consecutive tokens per lane into the ring cache.

    cache [B, S, KV, D(or 1)], val [B, T, KV, D], slot [B, T].  Slots are
    distinct within a lane whenever T <= S (speculative lanes never wrap
    — the engine's submit guard reserves prompt + max_new + k + 1 slots).
    """
    b = cache.shape[0]
    return cache.at[jnp.arange(b)[:, None], slot].set(
        val.astype(cache.dtype), unique_indices=True,
        indices_are_sorted=False)


def _verify_attend(q, k, v, position, cfg: ModelConfig, cache_len: int,
                   window=None):
    """Chunk-causal attention of T query tokens over the ring cache.

    q: [B, T, H, Dh]; k, v: [B, S, KV, Dh] (f32).  All T tokens' KV is
    already written, so slot contents correspond to ``final = position +
    T - 1``; query t at absolute position ``qpos = position + t`` then
    admits slots whose held position is in ``(qpos - window, qpos]`` —
    which excludes the later tokens of this same chunk (held > qpos) and
    reduces exactly to the single-token formula at T == 1."""
    b, t, hh, dh = q.shape
    s, kv = k.shape[1], k.shape[2]
    g = hh // kv
    qg = q.reshape(b, t, kv, g, dh).astype(jnp.float32)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, k.astype(jnp.float32))
    scores = scores / jnp.sqrt(dh).astype(jnp.float32)

    slots = jnp.arange(s)[None, :]                       # [1, S]
    final = (position + t - 1)[:, None]                  # [B, 1]
    cur_slot = final % s
    age = (cur_slot - slots) % s                         # 0 = newest
    held = final - age                                   # [B, S] absolute
    qpos = position[:, None] + jnp.arange(t)[None, :]    # [B, T]
    if window is None:
        window = cfg.window if cfg.window is not None else cache_len
    valid = ((held[:, None, :] >= 0)
             & (held[:, None, :] <= qpos[:, :, None])
             & (held[:, None, :] > qpos[:, :, None] - window))  # [B, T, S]
    scores = jnp.where(valid[:, None, None, :, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", p, v.astype(jnp.float32))
    return out.reshape(b, t, hh, dh).astype(q.dtype)


def _decode_attend(q, k, v, position, cfg: ModelConfig, cache_len: int,
                   window=None):
    """Attention of one query token over the ring cache.

    q: [B, 1, H, Dh]; k, v: [B, S, KV, Dh] (f32).  Valid slots: those
    holding positions in (pos - effective_window, pos].  ``window``: the
    layer's own (traced) window; None, ``cfg.window`` or the cache."""
    b, _, hh, dh = q.shape
    s, kv = k.shape[1], k.shape[2]
    g = hh // kv
    qg = q.reshape(b, kv, g, dh).astype(jnp.float32)
    scores = jnp.einsum("bghd,bsgd->bghs", qg, k.astype(jnp.float32))
    scores = scores / jnp.sqrt(dh).astype(jnp.float32)

    # slot i currently holds absolute position: the largest p <= position
    # with p % S == i  ->  valid iff that p > position - window and p >= 0
    slots = jnp.arange(s)[None, :]                       # [1, S]
    pos = position[:, None]                              # [B, 1]
    cur_slot = pos % s
    age = (cur_slot - slots) % s                         # 0 = newest
    held = pos - age                                     # absolute position
    if window is None:
        window = cfg.window if cfg.window is not None else cache_len
    valid = (held >= 0) & (held > pos - window)
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bghs,bsgd->bghd", p, v.astype(jnp.float32))
    return out.reshape(b, 1, hh, dh).astype(q.dtype)
