"""Core transformer layers: norms, RoPE, memory-efficient attention, MLP.

Pure-functional JAX (params are nested dicts).  Attention in the training /
prefill path is a chunked online-softmax ("flash") implementation in plain
jnp — bounded live memory under remat, the structure a TPU splash kernel
would have.  Decode attention lives in ``repro.models.blocks``: over the
paged int8 pool on a TPU, the Pallas kernel of
``repro.kernels.decode_attn`` reads each lane's live blocks in place;
every other decode path (the CPU, the ring cache, float32 pools, tensor
parallelism, speculative verify) gathers the cache and attends in jnp.
"""
from __future__ import annotations

import math
from functools import lru_cache
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import LAYER_KINDS, ModelConfig
from repro.models.sail_linear import mm
from repro.dist.sharding import maybe_constrain, tp_all_reduce

Initializer = jax.nn.initializers.Initializer


def dense_init(key, shape, fan_in=None, dtype=jnp.float32):
    fan_in = fan_in if fan_in is not None else shape[0]
    std = 1.0 / math.sqrt(fan_in)
    return jax.random.truncated_normal(key, -2, 2, shape, dtype) * std


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_init(cfg: ModelConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(p, x, cfg: ModelConfig):
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"] + p["bias"]
    else:
        var = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + cfg.norm_eps) * p["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope_freqs(cfg: ModelConfig, dtype=jnp.float32):
    d = cfg.head_dim
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    return inv.astype(dtype)


def yarn_freqs(cfg: ModelConfig) -> Tuple[np.ndarray, float]:
    """The full layers' YaRN inverse frequencies and attention factor,
    as transformers' ``_compute_yarn_parameters`` defines them: each
    frequency is extrapolated (kept) above the correction dim of
    ``beta_fast`` rotations, interpolated (divided by the factor) below
    that of ``beta_slow``, with a linear ramp between; cos and sin are
    then scaled by the attention factor."""
    d, base, f = cfg.head_dim, cfg.rope_theta, cfg.yarn_factor
    pos = base ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def corr(rotations):
        return (d * math.log(cfg.yarn_original_max
                             / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(cfg.yarn_beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    keep = 1.0 - ramp                  # 1: extrapolate, 0: interpolate
    inv = (1.0 / (f * pos)) * (1.0 - keep) + (1.0 / pos) * keep
    factor = cfg.yarn_attention_factor
    if factor is None:
        factor = 0.1 * math.log(f) + 1.0 if f > 1 else 1.0
    return inv.astype(np.float32), float(factor)


# the window of a layer that attends to the whole context
FULL_WINDOW = 1 << 30


@lru_cache(maxsize=None)
def _kind_tables(cfg: ModelConfig):
    """Per attention kind (0 sliding, 1 full): window, RoPE inverse
    frequencies and cos/sin scale; and each layer of the period's kind."""
    plain = (1.0 / (cfg.rope_theta ** (np.arange(0, cfg.head_dim, 2,
                                                 dtype=np.float64)
                                       / cfg.head_dim))).astype(np.float32)
    full, scale = (yarn_freqs(cfg) if cfg.yarn_factor is not None
                   else (plain, 1.0))
    windows = np.asarray([cfg.window or FULL_WINDOW, FULL_WINDOW], np.int32)
    kinds = np.asarray([LAYER_KINDS.index(t) for t in cfg.layer_types],
                       np.int32)
    return (windows, np.stack([plain, full]),
            np.asarray([1.0, scale], np.float32), kinds)


def layer_attention(cfg: ModelConfig, layer):
    """(window, rope) of layer ``layer`` (an absolute index, traced inside
    a layer scan) for a model whose layers differ in kind; ``rope`` is
    ``(inverse frequencies, cos/sin scale)`` for :func:`apply_rope`.
    (None, None) for a model without ``layer_types``: its one window and
    plain RoPE, as before."""
    if cfg.layer_types is None:
        return None, None
    if layer is None:
        raise ValueError("a model whose layers differ in kind "
                         "(layer_types) needs each layer's index")
    windows, inv, scale, kinds = _kind_tables(cfg)
    k = jnp.asarray(kinds)[layer % len(kinds)]
    return (jnp.asarray(windows)[k],
            (jnp.asarray(inv)[k], jnp.asarray(scale)[k]))


def layer_windows(cfg: ModelConfig, cache_len: int) -> np.ndarray:
    """Each layer's attention window at decode, [n_layers] int, on the
    host: what :func:`layer_attention` gives a model whose layers differ
    in kind, else ``cfg.window`` or, without one, ``cache_len``."""
    if cfg.layer_types is None:
        w = cfg.window if cfg.window is not None else cache_len
        return np.full((cfg.n_layers,), w, np.int64)
    windows, _, _, kinds = _kind_tables(cfg)
    return windows[kinds[np.arange(cfg.n_layers) % len(kinds)]].astype(
        np.int64)


def apply_rope(x: jax.Array, positions: jax.Array, cfg: ModelConfig,
               rope=None):
    """x: [B, T, H, Dh]; positions: [B, T] (absolute).  ``rope``: the
    layer's (inverse frequencies, cos/sin scale) from
    :func:`layer_attention`; None, the plain RoPE of ``cfg``."""
    inv = rope_freqs(cfg) if rope is None else rope[0]
    ang = positions[..., None].astype(jnp.float32) * inv  # [B, T, Dh/2]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    if rope is not None:
        sin, cos = sin * rope[1], cos * rope[1]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    sin, cos = sin[:, :, None, :], cos[:, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def sinusoidal_positions(seq: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / (10000.0 ** (dim / d))
    pe = jnp.zeros((seq, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(ang))
    pe = pe.at[:, 1::2].set(jnp.cos(ang))
    return pe


# ---------------------------------------------------------------------------
# Attention (GQA, optional qk-norm / sliding window / cross / bidirectional)
# ---------------------------------------------------------------------------

def attention_init(key, cfg: ModelConfig, cross: bool = False):
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    p = {
        "wq": dense_init(ks[0], (d, cfg.q_dim)),
        "wk": dense_init(ks[1], (d, cfg.kv_dim)),
        "wv": dense_init(ks[2], (d, cfg.kv_dim)),
        "wo": dense_init(ks[3], (cfg.q_dim, d), fan_in=cfg.q_dim),
    }
    if cfg.attention_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,))
        p["bk"] = jnp.zeros((cfg.kv_dim,))
        p["bv"] = jnp.zeros((cfg.kv_dim,))
        p["bo"] = jnp.zeros((d,))
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((cfg.head_dim,))}
        p["k_norm"] = {"scale": jnp.ones((cfg.head_dim,))}
    return p


def _qk_norm(x, scale, eps):
    var = (x.astype(jnp.float32) ** 2).mean(-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def flash_attention(q, k, v, *, causal: bool, window: Optional[int],
                    chunk: int, q_offset: int = 0,
                    kv_valid: Optional[jax.Array] = None,
                    q_chunk: int = 512):
    """Chunked online-softmax attention in pure jnp (q and kv blocked).

    q: [B, T, H, Dh]; k, v: [B, S, KV, Dh].  GQA via head grouping.
    The outer loop blocks queries (so the scan carry — and therefore the
    O(n_kv_chunks x carry) backward storage of lax.scan — is
    O(B*q_chunk*H*Dh), not O(B*T*H*Dh)); the inner scan walks KV blocks
    with a running (m, l, acc).  q_offset: absolute position of q[0]
    relative to k[0].  kv_valid: [B, S] bool padding mask.
    """
    b, t, h, dh = q.shape
    if t > q_chunk and t % q_chunk:
        # largest divisor of t <= q_chunk (vision prefixes give T=4672 etc)
        for d in range(q_chunk, 0, -1):
            if t % d == 0:
                q_chunk = d
                break
    if t > q_chunk and t % q_chunk == 0:
        nq = t // q_chunk
        qb = jnp.moveaxis(q.reshape(b, nq, q_chunk, h, dh), 1, 0)
        s_full = k.shape[1]

        if isinstance(window, int) and causal and kv_valid is None \
                and s_full > 2 * (window + q_chunk):
            # §Perf C1: sliding-window attention only needs KV in
            # [q_lo - window, q_hi); slice that band per q block instead of
            # masking the full quadratic sweep (16x fewer chunk passes at
            # 32k/window-1k).  Band length is static; offset is traced.
            band = -(-(window + q_chunk) // chunk) * chunk

            def one(args):
                qi, off = args
                lo = jnp.clip(off + q_chunk - band, 0, s_full - band)
                kb = jax.lax.dynamic_slice_in_dim(k, lo, band, 1)
                vb = jax.lax.dynamic_slice_in_dim(v, lo, band, 1)
                return flash_attention(qi, kb, vb, causal=causal,
                                       window=window, chunk=chunk,
                                       q_offset=off - lo, q_chunk=t)
        else:
            def one(args):
                qi, off = args
                return flash_attention(qi, k, v, causal=causal,
                                       window=window, chunk=chunk,
                                       q_offset=off, kv_valid=kv_valid,
                                       q_chunk=t)
        outs = jax.lax.map(
            jax.checkpoint(one),
            (qb, q_offset + jnp.arange(nq) * q_chunk))
        return jnp.moveaxis(outs, 0, 1).reshape(b, t, h, dh)
    s, kv = k.shape[1], k.shape[2]
    g = h // kv
    scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(b, t, kv, g, dh).astype(jnp.float32)

    n_chunks = -(-s // chunk)
    pad = n_chunks * chunk - s
    if pad:
        padkv = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k, v = padkv(k), padkv(v)
    if kv_valid is not None:
        kv_valid = jnp.pad(kv_valid, ((0, 0), (0, pad)),
                           constant_values=False)
    kc = k.reshape(b, n_chunks, chunk, kv, dh).astype(jnp.float32)
    vc = v.reshape(b, n_chunks, chunk, kv, dh).astype(jnp.float32)

    q_pos = q_offset + jnp.arange(t)

    def body(carry, inputs):
        m, l, acc = carry
        kb, vb, ci = inputs
        kv_pos = ci * chunk + jnp.arange(chunk)
        # scores: [B, T, KV, G, chunk]
        scores = jnp.einsum("btghd,bcgd->btghc", qg, kb) * scale
        valid = jnp.ones((b, t, chunk), bool)
        valid &= (kv_pos < s)[None, None, :]
        if causal:
            valid &= kv_pos[None, None, :] <= q_pos[None, :, None]
        if window is not None:
            valid &= kv_pos[None, None, :] > (q_pos[None, :, None] - window)
        if kv_valid is not None:
            vblk = jax.lax.dynamic_slice_in_dim(kv_valid, ci * chunk, chunk, 1)
            valid &= vblk[:, None, :]
        scores = jnp.where(valid[:, :, None, None, :], scores, -jnp.inf)
        m_cur = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        # guard -inf rows (fully masked chunk)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        p = jnp.exp(scores - m_safe[..., None])
        p = jnp.where(valid[:, :, None, None, :], p, 0.0)
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "btghc,bcgd->btghd", p, vb)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, t, kv, g), -jnp.inf)
    l0 = jnp.zeros((b, t, kv, g))
    acc0 = jnp.zeros((b, t, kv, g, dh))
    kc_t = jnp.moveaxis(kc, 1, 0)
    vc_t = jnp.moveaxis(vc, 1, 0)
    # remat each KV chunk: backward recomputes scores/p per chunk instead
    # of storing [B,T,H,chunk] residuals for every chunk simultaneously
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, acc0),
        (kc_t, vc_t, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, t, h, dh).astype(q.dtype)


def apply_attention(p, x, cfg: ModelConfig, *, positions, causal=True,
                    kv_x: Optional[jax.Array] = None,
                    kv_valid: Optional[jax.Array] = None,
                    window: Optional[int] = None, rope=None):
    """Full (prefill/train) attention.  kv_x given -> cross attention.
    ``window`` may be traced (a layer scan's per-layer window); ``rope``
    as :func:`apply_rope` takes it."""
    b, t, d = x.shape
    src = kv_x if kv_x is not None else x
    q = mm(x, p["wq"])
    k = mm(src, p["wk"])
    v = mm(src, p["wv"])
    if cfg.attention_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, t, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, src.shape[1], cfg.n_kv, cfg.head_dim)
    v = v.reshape(b, src.shape[1], cfg.n_kv, cfg.head_dim)
    q = maybe_constrain(q, "batch", None, "model", None)
    k = maybe_constrain(k, "batch", None, "model", None)
    v = maybe_constrain(v, "batch", None, "model", None)
    if cfg.qk_norm:
        q = _qk_norm(q, p["q_norm"]["scale"], cfg.norm_eps)
        k = _qk_norm(k, p["k_norm"]["scale"], cfg.norm_eps)
    if cfg.pos == "rope" and kv_x is None:
        q = apply_rope(q, positions, cfg, rope)
        k = apply_rope(k, positions, cfg, rope)
    out = flash_attention(q, k, v, causal=causal and kv_x is None,
                          window=window, chunk=cfg.attn_chunk,
                          kv_valid=kv_valid)
    out = maybe_constrain(out, "batch", None, "model", None)
    out = tp_all_reduce(mm(out.reshape(b, t, cfg.q_dim), p["wo"]))
    if cfg.attention_bias:
        out = out + p["bo"]
    return out


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.act in ("swiglu", "geglu"):
        return {"w_gate": dense_init(ks[0], (d, f)),
                "w_up": dense_init(ks[1], (d, f)),
                "w_down": dense_init(ks[2], (f, d), fan_in=f)}
    return {"w_up": dense_init(ks[0], (d, f)),
            "w_down": dense_init(ks[1], (f, d), fan_in=f)}


def apply_mlp(p, x, cfg: ModelConfig):
    if cfg.act == "swiglu":
        h = jax.nn.silu(mm(x, p["w_gate"])) * mm(x, p["w_up"])
    elif cfg.act == "geglu":
        h = jax.nn.gelu(mm(x, p["w_gate"])) * mm(x, p["w_up"])
    else:
        h = jax.nn.gelu(mm(x, p["w_up"]))
    h = maybe_constrain(h, "batch", None, "model")
    return tp_all_reduce(mm(h, p["w_down"]))
