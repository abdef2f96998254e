"""Mixture-of-Experts layer (granite-moe 32e top-8, mixtral 8e top-2,
mellum2 64e top-8).

Three dispatch modes:
  * "routed": dropless serving form — each token's top-k (token, expert)
    pairs are sorted by expert and every projection is one grouped
    matmul over the sorted rows (``jax.lax.ragged_dot``), so FLOPs scale
    with top_k, not with E, and no token is dropped.  Prefill, decode
    and verify run it.
  * "dispatch": GShard-style capacity-based token dispatch (one-hot combine
    tensors, einsum over expert-major buffers).  FLOPs scale with top_k and
    capacity_factor — used for training where efficiency matters; experts
    shard over the mesh 'model' axis (EP) or within-expert FFN dim (TP),
    per cfg.moe_shard.
  * "dense": every expert computed for every token, combined by routing
    weights — exact (no capacity drops), the CPU tests' oracle for the
    other two.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig
from repro.models.layers import dense_init
from repro.models.sail_linear import einsum_q, mm, ragged_q
from repro.dist.sharding import maybe_constrain


def moe_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    d, f, e = cfg.d_model, cfg.expert_ffn, cfg.n_experts
    return {
        "router": dense_init(ks[0], (d, e)),
        "w_gate": dense_init(ks[1], (e, d, f)),
        "w_up": dense_init(ks[2], (e, d, f)),
        "w_down": dense_init(ks[3], (e, f, d), fan_in=f),
    }


def _router_probs(p, x, cfg: ModelConfig):
    logits = mm(x, p["router"])                              # [..., E]
    topv, topi = jax.lax.top_k(logits, cfg.top_k)
    probs = jax.nn.softmax(topv, axis=-1)                 # renormalized top-k
    return logits, topv, topi, probs


def apply_moe_dense(p, x, cfg: ModelConfig):
    """Exact dense-compute MoE: all experts, weighted by top-k router."""
    *lead, d = x.shape
    xt = x.reshape(-1, d)
    logits, _, topi, probs = _router_probs(p, xt, cfg)
    # combine weights over all experts: [T, E]
    comb = (jax.nn.one_hot(topi, cfg.n_experts) * probs[..., None]).sum(-2)
    if cfg.act == "gelu":
        h = jax.nn.gelu(einsum_q("td,edf->tef", xt, p["w_up"]))
    else:
        h = jax.nn.silu(einsum_q("td,edf->tef", xt, p["w_gate"])) * \
            einsum_q("td,edf->tef", xt, p["w_up"])
    y = einsum_q("tef,efd->ted", h, p["w_down"])        # [T, E, D]
    out = jnp.einsum("ted,te->td", y, comb)
    return out.reshape(*lead, d), _aux_loss(logits, comb, cfg)


def apply_moe_routed(p, x, cfg: ModelConfig, valid=None):
    """Dropless routed MoE: top-k over a float32 router, the (token,
    expert) pairs sorted by expert, one grouped matmul per projection
    over the sorted rows, and the outputs combined with the renormalized
    top-k weights.  Returns (out, tokens per expert [E] int32), the
    counts over the rows ``valid`` ([T] bool, every row where None) —
    what the engine accumulates as ``moe.*`` counters.  Every op sits in
    the ``moe`` scope of a profiler trace."""
    with jax.named_scope("moe"):
        *lead, d = x.shape
        xt = x.reshape(-1, d)
        t, k = xt.shape[0], cfg.top_k
        # full float32 (never quantized): the router picks discrete experts
        logits = jnp.dot(xt.astype(jnp.float32), p["router"],
                         precision=jax.lax.Precision.HIGHEST)
        topv, topi = jax.lax.top_k(logits, k)
        probs = jax.nn.softmax(topv, axis=-1)             # renormalized
        flat = topi.reshape(-1)                           # [T*k]
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=cfg.n_experts)
        rows = xt[order // k]                             # sorted by expert
        if cfg.act == "gelu":
            h = jax.nn.gelu(ragged_q(rows, p["w_up"], sizes))
        else:
            h = jax.nn.silu(ragged_q(rows, p["w_gate"], sizes)) * \
                ragged_q(rows, p["w_up"], sizes)
        y = ragged_q(h, p["w_down"], sizes)[jnp.argsort(order)]
        out = jnp.einsum("tkd,tk->td", y.reshape(t, k, d),
                         probs.astype(y.dtype))
        if valid is None:
            counts = sizes
        else:
            counts = jnp.bincount(
                flat, weights=jnp.repeat(valid.reshape(-1), k).astype(
                    jnp.int32), length=cfg.n_experts)
        return out.reshape(*lead, d).astype(x.dtype), counts.astype(jnp.int32)


MOE_GROUP_TOKENS = 512   # GShard dispatch group; the dispatch tensor is
# tokens x E x cap with cap = cf*tg*k/E, so bytes scale LINEARLY with
# tg — 512 keeps it ~4x smaller than 2048 at slightly higher drop
# variance (dry-run memory analysis, granite 32-expert cells)


def apply_moe_dispatch(p, x, cfg: ModelConfig):
    """Capacity-based dispatch (GShard): tokens are split into groups of
    ~MOE_GROUP_TOKENS; each group routes to per-expert buffers of capacity
    ``cf * group * k / E``.  The dispatch tensor is built per top-k slot
    ([G, T_g, E, C] never materializes with a K axis, and T_g bounds the
    quadratic T*C term) — without grouping, 32k tokens/device would need a
    multi-TB one-hot, which the dry-run memory analysis caught."""
    *lead, d = x.shape
    xt = x.reshape(-1, d)
    t = xt.shape[0]
    e = cfg.n_experts
    tg = min(MOE_GROUP_TOKENS, t)
    if t % tg:
        tg = t  # fall back to one group for odd tiny batches
    ng = t // tg
    cap = max(1, int(cfg.capacity_factor * tg * cfg.top_k / e))

    logits, _, topi, probs = _router_probs(p, xt, cfg)    # topi [T, K]
    topi_g = maybe_constrain(topi.reshape(ng, tg, cfg.top_k),
                             "batch", None, None)
    probs_g = maybe_constrain(probs.reshape(ng, tg, cfg.top_k),
                              "batch", None, None)
    xg = maybe_constrain(xt.reshape(ng, tg, d), "batch", None, None)

    # buffer position per (group, token, k): cumulative count of earlier
    # (token, k) pairs routed to the same expert within the group
    onehot = jax.nn.one_hot(topi_g, e, dtype=jnp.int32)   # [G, Tg, K, E]
    flat = onehot.reshape(ng, tg * cfg.top_k, e)
    pos = (jnp.cumsum(flat, axis=1) * flat - 1).reshape(
        ng, tg, cfg.top_k, e)
    pos_k = jnp.take_along_axis(
        pos, topi_g[..., None], axis=-1)[..., 0]          # [G, Tg, K]
    in_cap = (pos_k < cap) & (pos_k >= 0)

    dtype = x.dtype

    @jax.checkpoint  # recompute the one-hots in backward: saving the
    def _build_dispatch(topi_g, pos_k, in_cap, probs_g):
        # per-k contrib tensors for bwd costs top_k x |disp| (tens of GB
        # for 32-expert models — caught by the dry-run memory analysis)
        disp = jnp.zeros((ng, tg, e, cap), dtype)
        comb = jnp.zeros((ng, tg, e, cap), jnp.float32)
        for k in range(cfg.top_k):                        # small static K
            oh_e = jax.nn.one_hot(topi_g[..., k], e, dtype=dtype)
            oh_c = jax.nn.one_hot(pos_k[..., k], cap, dtype=dtype)
            m = in_cap[..., k].astype(dtype)[..., None, None]
            contrib = oh_e[..., :, None] * oh_c[..., None, :] * m
            disp = disp + contrib
            comb = comb + contrib.astype(jnp.float32) * \
                probs_g[..., k, None, None]
        return disp, comb

    disp, comb = _build_dispatch(topi_g, pos_k, in_cap, probs_g)

    disp = maybe_constrain(disp, "batch", None, None, None)
    comb = maybe_constrain(comb, "batch", None, None, None)
    xe = jnp.einsum("gtd,gtec->gecd", xg, disp)           # [G, E, C, D]
    xe = maybe_constrain(xe, "batch", None, None, None)
    if cfg.act == "gelu":
        h = jax.nn.gelu(einsum_q("gecd,edf->gecf", xe, p["w_up"]))
    else:
        h = jax.nn.silu(einsum_q("gecd,edf->gecf", xe, p["w_gate"])) * \
            einsum_q("gecd,edf->gecf", xe, p["w_up"])
    ye = einsum_q("gecf,efd->gecd", h, p["w_down"])
    # NOTE (§Perf B1, refuted): forcing a reduce-scatter onto ye's D here
    # (maybe_constrain(ye, "batch", None, None, "model")) was predicted to
    # cut the row-parallel AR by ~2.5x (token-shaped vs buffer-shaped
    # payload) but GSPMD responded with an extra buffer-shaped AR on the
    # dispatch tensors plus two backward all-gathers: measured collective
    # bytes +43%.  Kept off; see EXPERIMENTS.md §Perf.
    out = jnp.einsum("gecd,gtec->gtd", ye,
                     comb.astype(ye.dtype)).reshape(t, d)
    comb_e = comb.sum(-1).reshape(t, e)                   # [T, E]
    return (out.reshape(*lead, d).astype(x.dtype),
            _aux_loss(logits, comb_e, cfg))


def _aux_loss(logits, comb, cfg: ModelConfig):
    """Switch-style load-balancing auxiliary loss."""
    probs = jax.nn.softmax(logits, axis=-1)
    frac_tokens = (comb > 0).astype(jnp.float32).mean(0)   # [E]
    frac_probs = probs.mean(0)
    return cfg.n_experts * jnp.sum(frac_tokens * frac_probs)


def apply_moe(p, x, cfg: ModelConfig, mode: str = "dispatch"):
    """(out, aux loss); "routed", a serving form, has no aux loss."""
    if mode == "routed":
        return apply_moe_routed(p, x, cfg)[0], jnp.zeros((), jnp.float32)
    if mode == "dense":
        return apply_moe_dense(p, x, cfg)
    return apply_moe_dispatch(p, x, cfg)
