"""Plain reference for a dense decoder with grouped-query attention.

Covers the Mistral / Llama layer: RMSNorm before attention and MLP, RoPE
(rotate-half form), grouped-query attention with an optional sliding
window, a SwiGLU MLP and an untied output head.  It is written from the
published layer equations in plain ``jax.numpy``, at float32 and
``highest`` matmul precision, one sequence at a time, with no cache and
no batching.  It imports nothing of the system under test.

The served path stores its numbers in lower precision in three places,
and the reference applies the same rounding where the configuration
states it:

- weights: symmetric round-to-nearest over groups of ``group_size`` rows
  of each ``W[K, N]``, scale = group absmax, ``2**(b-1) - 1`` levels a
  side (``weight_bits``);
- activations entering each matmul: per-token symmetric rounding to
  ``act_bits`` (scale = absmax / (2**(a-1) - 1));
- the KV cache: per token and KV head, int8 over the head dimension
  (``kv_bits`` 8).  Prompt positions attend over the keys and values the
  prompt pass computed; every later position attends over the cached
  int8 copies, its own included, because that is what a decode step
  reads.

``dtype: "bfloat16"`` computes the whole forward in bfloat16 (weights,
activations and the residual stream; norms and softmax in float32), the
precision below float32 that the benchmark's control uses.

``make_params`` builds the weights from a seed on the device in one
jitted call, in the parameter layout the serving entry takes.  The
reference calls it again after the run instead of reading the system's
weights.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import head_dim, layer_matmuls

def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer seed (64 bits and more:
    ``jax.random.PRNGKey`` alone keeps only the low 32)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(
        jnp.asarray(words, jnp.uint32), impl="threefry2x32")


@partial(jax.jit, static_argnums=(0,))
def _make(frozen, key):
    m = dict(frozen)
    L, d, V = m["n_layers"], m["d_model"], m["vocab"]
    keys = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return jax.random.normal(next(keys), shape, jnp.float32) * std

    def norm_scale(shape):
        # near one, so that a path that skips the scale is seen
        return 1.0 + normal(shape, 0.1)

    attn, mlp = {}, {}
    for name, (k, n) in layer_matmuls(m).items():
        w = normal((L, k, n), 1.0 / math.sqrt(k))
        (attn if name in ("wq", "wk", "wv", "wo") else mlp)[name] = w
    return {
        "embed": normal((V, d), 1.0),
        "blocks": {"attn_norm": {"scale": norm_scale((L, d))},
                   "attn": attn,
                   "mlp_norm": {"scale": norm_scale((L, d))},
                   "mlp": mlp},
        "final_norm": {"scale": norm_scale((d,))},
        "lm_head": normal((d, V), 1.0 / math.sqrt(d)),
    }


def make_params(m: Dict[str, Any], seed: int):
    """Float32 weights for model dict ``m`` from ``seed``, made on the
    default device in one jitted call."""
    return _make(tuple(sorted(m.items())), seed_key(seed))


# ---------------------------------------------------------------------------
# rounding the configuration states
# ---------------------------------------------------------------------------

def quant_weight(w, bits: int, group: int):
    """Round ``w[K, N]`` to ``bits`` per group of ``group`` rows."""
    k, n = w.shape
    levels = (1 << (bits - 1)) - 1
    wg = w.reshape(k // group, group, n)
    scale = jnp.max(jnp.abs(wg), axis=1, keepdims=True)
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(wg / scale * levels), -levels, levels)
    return (q / levels * scale).reshape(k, n)


def quant_rows(x, bits: int):
    """Per-row symmetric rounding to ``bits``: (integer codes, scale)."""
    levels = (1 << (bits - 1)) - 1
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax) / levels
    return jnp.clip(jnp.round(x / scale), -levels, levels), scale


def _matmul(x, w, prec):
    dt = prec["dtype"]
    if prec.get("act_bits"):
        q, s = quant_rows(x.astype(jnp.float32), prec["act_bits"])
        y = jnp.dot(q.astype(dt), w, preferred_element_type=jnp.float32)
        return (y * s).astype(dt)
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(dt)


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _rope(x, theta):
    """x [T, H, Dh] at positions 0..T-1, rotate-half convention."""
    t, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _kv_round(x):
    """int8 over the head dimension, per position and KV head."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _attend(q, k, v, mask):
    """q [T, H, Dh], k/v [T, KV, Dh], mask [T, T] -> [T, H, Dh]."""
    t, h, dh = q.shape
    kvh = k.shape[1]
    qg = q.reshape(t, kvh, h // kvh, dh)
    s = jnp.einsum("tkgd,skd->kgts", qg, k) / math.sqrt(dh)
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("kgts,skd->tkgd", p, v).reshape(t, h, dh)


@partial(jax.jit, static_argnums=(3, 4))
def _forward(params, tokens, n_prompt, frozen_m, frozen_prec):
    m, prec = dict(frozen_m), dict(frozen_prec)
    dt = prec["dtype"]
    t = tokens.shape[0]
    hd = head_dim(m)
    eps = m["norm_eps"]
    wb, group = prec.get("weight_bits"), prec["group_size"]

    def w_(w):
        w = w.astype(jnp.float32)
        if wb:
            w = quant_weight(w, wb, group)
        return w.astype(dt)

    pos = jnp.arange(t)
    causal = pos[None, :] <= pos[:, None]
    if m.get("window"):
        causal &= pos[None, :] > pos[:, None] - m["window"]
    decode_row = (pos >= n_prompt)[:, None, None]

    def layer(x, a):
        h = _rms(x, a["attn_norm"]["scale"], eps)
        q = _matmul(h, w_(a["attn"]["wq"]), prec).reshape(t, -1, hd)
        k = _matmul(h, w_(a["attn"]["wk"]), prec).reshape(t, -1, hd)
        v = _matmul(h, w_(a["attn"]["wv"]), prec).reshape(t, -1, hd)
        q = _rope(q, m["rope_theta"]).astype(dt)
        k = _rope(k, m["rope_theta"]).astype(dt)
        out = _attend(q, k, v, causal)
        if prec.get("kv_bits") == 8:
            kq = _kv_round(k.astype(jnp.float32)).astype(dt)
            vq = _kv_round(v.astype(jnp.float32)).astype(dt)
            out = jnp.where(decode_row, _attend(q, kq, vq, causal), out)
        o = _matmul(out.reshape(t, -1).astype(dt), w_(a["attn"]["wo"]), prec)
        x = (x + o).astype(dt)
        h = _rms(x, a["mlp_norm"]["scale"], eps)
        g = _matmul(h, w_(a["mlp"]["w_gate"]), prec)
        u = _matmul(h, w_(a["mlp"]["w_up"]), prec)
        y = _matmul((jax.nn.silu(g.astype(jnp.float32)) * u).astype(dt),
                    w_(a["mlp"]["w_down"]), prec)
        return (x + y).astype(dt), None

    x, _ = jax.lax.scan(layer, params["embed"][tokens].astype(dt),
                        params["blocks"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    return _matmul(x, w_(params["lm_head"]), prec).astype(jnp.float32)


def logits(params, tokens, n_prompt: int, m: Dict[str, Any],
           prec: Dict[str, Any]):
    """Logits ``[T, V]`` of one sequence ``tokens [T]`` (prompt of
    ``n_prompt`` tokens, then served tokens), teacher-forced.

    ``prec`` holds ``weight_bits`` (None: unrounded), ``act_bits``,
    ``group_size``, ``kv_bits`` (8 or 32) and ``dtype``."""
    p = dict(prec)
    p["dtype"] = jnp.dtype(p.get("dtype", "float32")).name
    p = {k: v for k, v in p.items() if v is not None}
    with jax.default_matmul_precision("highest"):
        return _forward(params, jnp.asarray(tokens, jnp.int32),
                        jnp.int32(n_prompt), tuple(sorted(m.items())),
                        tuple(sorted(p.items())))


def gaps(ref_logits, chosen) -> np.ndarray:
    """How far below the reference's best logit each chosen token lies."""
    ref = np.asarray(ref_logits, np.float64)
    idx = np.asarray(chosen, np.int64)
    return ref.max(axis=-1) - ref[np.arange(len(idx)), idx]
