"""Plain reference for a decoder of sparse-expert layers whose attention
mixes sliding-window and full layers (Mellum2-12B-A2.5B).

Each layer: RMSNorm, grouped-query attention with RoPE (rotate-half
form), RMSNorm, and a mixture of SiLU-gated experts.  Attention kinds
follow ``layer_types``, one period repeated over the depth: a
``sliding`` layer attends to the last ``window`` positions under the
plain RoPE of ``rope_theta``; a ``full`` layer attends to every earlier
position under YaRN (transformers' ``_compute_yarn_parameters``: each
frequency kept above the correction dim of ``yarn_beta_fast`` rotations,
divided by ``yarn_factor`` below that of ``yarn_beta_slow``, a linear
ramp between, cos and sin scaled by the attention factor).  The router
is a float32 linear map; each token goes to its ``top_k`` experts with
their softmax weights renormalized over the chosen ones, and each
expert runs only on the tokens routed to it.  Written from the
published equations in plain ``jax.numpy`` at float32 and ``highest``
matmul precision, one sequence at a time, one layer at a time, queries
in blocks, with no cache and no batching.  It imports nothing of the
system under test; it reuses the activation rounding, the norm and the
gap of ``dense_gqa``.

Departures from the published model: the family's MTP head has no key
in the config and is left out, as serving leaves it out; ``qk_norm`` is
taken as absent and the router's score function as softmax, both
unstated by the config.

The plan's rounding, where the precision states it, as ``dense_gqa``
applies it: weights (not the router, which stays float32) symmetric
round-to-nearest over groups of ``group_size`` rows, the activations
entering each rounded matmul per token to ``act_bits`` (an expert's
input per routed row), and int8 keys and values per position and KV
head for every position a decode step reads (the prompt's own rows
attend over the unrounded keys and values, as prefill does).

``make_params`` makes the weights from the seed on the device one layer
and matrix at a time and keeps them on the host as NumPy arrays, in the
parameter layout the serving entry takes, so that the float32 model
never sits on the device whole.  ``logits`` keeps each matrix's rounded
codes on the device after its first call on a weight tree, so the
requests of one check move the weights once.
"""
from __future__ import annotations

import gc
import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense_gqa import (_kv_round, _matmul, _rms,  # noqa: F401
                                       gaps, seed_key)

Q_BLOCK = 512          # query rows per attention block


def _shape(m: Dict[str, Any]):
    hd = m["d_head"] or m["d_model"] // m["n_heads"]
    return (m["n_layers"], m["d_model"], m["vocab"], m["n_experts"],
            m["moe_ffn"] or m["d_ff"], hd, m["n_heads"] * hd, m["n_kv"] * hd)


@partial(jax.jit, static_argnums=(1,))
def _normal(key, shape, std, mean):
    return mean + std * jax.random.normal(key, shape, jnp.float32)


def make_params(m: Dict[str, Any], seed: int):
    """Float32 weights for model dict ``m`` from ``seed``, as NumPy.
    Rounded codes kept on the device for earlier weights are let go,
    those of a copy of this module loaded for an earlier check too: the
    harness loads the file anew for each use, and such a copy holds its
    codes until the cycle collector frees it (7.4 GB for a check with
    the control, enough to stop the next seed's engine in one process)."""
    _KEPT.clear()
    gc.collect()
    L, d, V, E, f, _, q, kv = _shape(m)
    root = seed_key(seed)
    leaf_id = iter(range(1, 64))

    def stack(shape, std, mean=0.0):
        key = jax.random.fold_in(root, next(leaf_id))
        out = np.empty((L,) + shape, np.float32)
        for layer in range(L):
            out[layer] = np.asarray(_normal(jax.random.fold_in(key, layer),
                                            shape, std, mean))
        return out

    def one(shape, std, mean=0.0):
        return np.asarray(_normal(jax.random.fold_in(root, next(leaf_id)),
                                  shape, std, mean))

    # norm scales near one, so that a path that skips the scale is seen
    return {
        "embed": one((V, d), 1.0),
        "blocks": {
            "attn_norm": {"scale": stack((d,), 0.1, 1.0)},
            "attn": {"wq": stack((d, q), d ** -0.5),
                     "wk": stack((d, kv), d ** -0.5),
                     "wv": stack((d, kv), d ** -0.5),
                     "wo": stack((q, d), q ** -0.5)},
            "mlp_norm": {"scale": stack((d,), 0.1, 1.0)},
            "moe": {"router": stack((d, E), d ** -0.5),
                    "w_gate": stack((E, d, f), d ** -0.5),
                    "w_up": stack((E, d, f), d ** -0.5),
                    "w_down": stack((E, f, d), f ** -0.5)},
        },
        "final_norm": {"scale": one((d,), 0.1, 1.0)},
        "lm_head": one((d, V), d ** -0.5),
    }


# ---------------------------------------------------------------------------
# weights as the plan rounds them, kept on the device between calls
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=(1, 2))
def _codes(w, bits: int, group: int):
    """Symmetric round-to-nearest of ``w[.., K, N]`` over groups of
    ``group`` rows: (int8 codes, group scales)."""
    *lead, k, n = w.shape
    levels = (1 << (bits - 1)) - 1
    wg = w.reshape(*lead, k // group, group, n)
    scale = jnp.max(jnp.abs(wg), axis=-2, keepdims=True)
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(wg / scale * levels), -levels, levels)
    return q.astype(jnp.int8), scale


_KEPT: Dict[Any, Any] = {}


def _weight(c, bits: int):
    """The rounded float32 weight of ``_codes``'s (codes, scales)."""
    q, scale = c
    levels = (1 << (bits - 1)) - 1
    w = q.astype(jnp.float32) / levels * scale
    return w.reshape(w.shape[:-3] + (-1, w.shape[-1]))


def _rounded(params, prec: Dict):
    """Each rounded matrix's codes on the device, per layer (and the
    head's), made on the first call for this weight tree and precision;
    None where the precision rounds no weight."""
    bits = prec.get("weight_bits")
    if not bits:
        return None
    key = (id(params), bits, prec["group_size"])
    if key not in _KEPT:
        for k in [k for k in _KEPT if k[0] != id(params)]:
            del _KEPT[k]
        g = prec["group_size"]
        b = params["blocks"]
        layers = []
        for layer in range(b["attn"]["wq"].shape[0]):
            mats = {name: b["attn"][name][layer]
                    for name in ("wq", "wk", "wv", "wo")}
            mats.update({name: b["moe"][name][layer]
                         for name in ("w_gate", "w_up", "w_down")})
            layers.append({name: _codes(jnp.asarray(w), bits, g)
                           for name, w in mats.items()})
        head = _codes(jnp.asarray(params["lm_head"]), bits, g)
        # the tree is kept too, so that its id stays its own
        _KEPT[key] = (params, layers, head)
    return _KEPT[key][1:]


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def rope_freqs(m: Dict[str, Any], kind: str):
    """(inverse frequencies, cos/sin scale) of a layer of ``kind``."""
    hd, base = _shape(m)[5], m["rope_theta"]
    pos = base ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    if kind == "sliding" or not m.get("yarn_factor"):
        return (1.0 / pos).astype(np.float32), 1.0
    factor, orig = m["yarn_factor"], m["yarn_original_max"]

    def correction_dim(rotations):
        return hd * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(m["yarn_beta_fast"])), 0)
    high = min(math.ceil(correction_dim(m["yarn_beta_slow"])), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0.0, 1.0)
    extrapolate = 1.0 - ramp
    inv = (1.0 / (factor * pos)) * ramp + (1.0 / pos) * extrapolate
    scale = m.get("yarn_attention_factor") or (
        0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0)
    return inv.astype(np.float32), float(scale)


def _rope(x, inv, scale):
    """x [T, H, Dh] at positions 0..T-1."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    sin = (jnp.sin(ang) * scale)[:, None]
    cos = (jnp.cos(ang) * scale)[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, rows, window):
    """Queries ``q [t, H, Dh]`` at positions ``rows`` over keys and
    values ``[T, KV, Dh]`` at positions 0..T-1: causal, and within
    ``window`` where it is not None."""
    t, h, dh = q.shape
    kvh = k.shape[1]
    cols = jnp.arange(k.shape[0])
    mask = cols[None, :] <= rows[:, None]
    if window is not None:
        mask &= cols[None, :] > rows[:, None] - window
    s = jnp.einsum("tkgd,skd->kgts", q.reshape(t, kvh, h // kvh, dh),
                   k) / math.sqrt(dh)
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("kgts,skd->tkgd", p, v).reshape(t, h, dh)


@partial(jax.jit, static_argnums=(9, 10, 11))
def _attention(x, norm, wq, wk, wv, wo, inv, scale, n_prompt, window,
               frozen_prec, hd):
    prec = dict(frozen_prec)
    t = x.shape[0]
    h = _rms(x, norm, prec["eps"])
    q = _rope(_matmul(h, wq, prec).reshape(t, -1, hd), inv, scale)
    k = _rope(_matmul(h, wk, prec).reshape(t, -1, hd), inv, scale)
    v = _matmul(h, wv, prec).reshape(t, -1, hd)
    kq, vq = _kv_round(k), _kv_round(v)
    pad = -t % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, Q_BLOCK, q.shape[1], hd)

    def block(args):
        qi, i = args
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        out = _attend(qi, k, v, rows, window)
        if prec.get("kv_bits") == 8:
            # a decode step reads every position from the int8 cache
            decoded = _attend(qi, kq, vq, rows, window)
            out = jnp.where((rows >= n_prompt)[:, None, None], decoded, out)
        return out

    out = jax.lax.map(block, (qb, jnp.arange(qb.shape[0])))
    out = out.reshape(-1, q.shape[1] * hd)[:t]
    return x + _matmul(out, wo, prec)


# ---------------------------------------------------------------------------
# experts
# ---------------------------------------------------------------------------

def route(logits, k: int):
    """Each token's ``k`` experts and their weights: the softmax over
    the chosen experts' logits (``norm_topk_prob``)."""
    top, idx = jax.lax.top_k(logits, k)
    return idx, jax.nn.softmax(top, axis=-1)


@partial(jax.jit, static_argnums=(7,), donate_argnums=(0,))
def _expert(out, h, rows, weight, w_gate, w_up, w_down, frozen_prec):
    """Add one expert's output, weighted, to the rows routed to it."""
    prec = dict(frozen_prec)
    x = h[rows]
    g = _matmul(x, w_gate, prec)
    u = _matmul(x, w_up, prec)
    y = _matmul(jax.nn.silu(g) * u, w_down, prec)
    return out.at[rows].add(y * weight[:, None])


def _bucket(n: int) -> int:
    """Rows an expert call is padded to: few shapes, few compiles."""
    return max(128, 1 << (n - 1).bit_length()) if n < 1024 else \
        -(-n // 1024) * 1024


def _experts(x, moe, layer_w, m, prec, frozen):
    h = _rms(x, moe["norm"], prec["eps"])
    logits = jnp.dot(h, jnp.asarray(moe["router"]),
                     precision=jax.lax.Precision.HIGHEST)
    idx, wts = route(logits, m["top_k"])
    idx, wts = np.asarray(idx), np.asarray(wts)
    out = jnp.zeros_like(x)
    for e in range(m["n_experts"]):
        tok, slot = np.nonzero(idx == e)
        if not tok.size:
            continue
        n = _bucket(tok.size)
        rows = np.zeros(n, np.int32)
        rows[:tok.size] = tok
        weight = np.zeros(n, np.float32)
        weight[:tok.size] = wts[tok, slot]
        mats = [layer_w[name][e] for name in ("w_gate", "w_up", "w_down")]
        out = _expert(out, h, jnp.asarray(rows), jnp.asarray(weight),
                      *mats, frozen)
    return x + out


def logits(params, tokens, n_prompt: int, m: Dict[str, Any],
           prec: Dict[str, Any]):
    """Logits ``[T, V]`` of one sequence ``tokens [T]`` (prompt of
    ``n_prompt`` tokens, then served tokens), teacher-forced.

    ``prec`` holds ``weight_bits`` (None: unrounded), ``act_bits``,
    ``group_size``, ``kv_bits`` (8 or 32) and ``dtype`` (float32)."""
    p = {k: v for k, v in dict(prec).items() if v is not None}
    p["dtype"] = jnp.dtype(p.get("dtype", "float32")).name
    p["eps"] = m["norm_eps"]
    frozen = tuple(sorted(p.items()))
    L, hd = m["n_layers"], _shape(m)[5]
    kinds = m["layer_types"]
    b = params["blocks"]
    with jax.default_matmul_precision("highest"):
        kept = _rounded(params, p)
        bits = p.get("weight_bits")

        def w_(layer, name, host):
            if kept is None:
                return jnp.asarray(host)
            return _weight(kept[0][layer][name], bits)

        tokens = np.asarray(tokens, np.int64)
        x = jnp.asarray(params["embed"][tokens])
        for layer in range(L):
            kind = kinds[layer % len(kinds)]
            inv, scale = rope_freqs(m, kind)
            a = b["attn"]
            x = _attention(
                x, jnp.asarray(b["attn_norm"]["scale"][layer]),
                *(w_(layer, n, a[n][layer]) for n in ("wq", "wk", "wv",
                                                      "wo")),
                jnp.asarray(inv), jnp.float32(scale), jnp.int32(n_prompt),
                m["window"] if kind == "sliding" else None, frozen, hd)
            moe = b["moe"]
            if kept is None:
                layer_w = {n: jnp.asarray(moe[n][layer])
                           for n in ("w_gate", "w_up", "w_down")}
            else:
                layer_w = {n: _weight(kept[0][layer][n], bits)
                           for n in ("w_gate", "w_up", "w_down")}
            x = _experts(x, {"norm": jnp.asarray(b["mlp_norm"]["scale"][layer]),
                             "router": moe["router"][layer]},
                         layer_w, m, p, frozen)
        x = _rms(x, jnp.asarray(params["final_norm"]["scale"]), p["eps"])
        head = (jnp.asarray(params["lm_head"]) if kept is None
                else _weight(kept[1], bits))
        return _matmul(x, head, p).astype(jnp.float32)
