"""Tensor-parallel quantized serving: the LUT weight tree sharded over a mesh.

SAIL's premise is that quantized LUT-GEMV makes commodity hardware serve
LLMs economically — but one device caps the model size and tokens/s this
reproduction can claim.  This module shards the *quantized* weight tree
itself over a ``make_mesh((1, M), ("data", "model"))`` mesh, Megatron
style, and runs the serving entry points (decode step, slot/paged
prefill) under ``shard_map`` so each shard executes the existing LUT-GEMV
kernels on its slice unchanged:

  * column-parallel (``wq/wk/wv/w_gate/w_up``): output dim on the model
    axis — each shard owns ``n_heads/M`` query heads, ``n_kv/M`` KV
    heads, and ``d_ff/M`` of the gate/up projection;
  * row-parallel (``wo``, ``w_down``): reduction dim on the model axis,
    partial sums combined by one ``psum`` per attention and one per MLP
    (the ``tp_all_reduce`` hooks in ``repro.models``);
  * quantized leaves: packed codes and group scales partition along the
    same logical axes as the matrix they encode (group quantization is
    per-group independent, so a contiguous K-slice carries exactly its
    own groups' codes and scales); codebooks and every 1-D param are
    replicated;
  * embeddings, ``lm_head``, and norms are replicated, so logits are
    computed redundantly on every shard and greedy decode is trivially
    shard-count invariant;
  * the KV pool shards on the kv-head axis (axis 3 of the ring
    ``[L, B, S, n_kv, Dh]`` layout, the heads-major last axis of the
    lane-dense paged ``[L, NB, BS, n_kv*Dh]`` one)
    — block tables, lengths, and all pool *accounting* stay logical and
    replicated, so the engine's scheduler/block manager never see TP.

``wire_bits=8`` sends int8+scale compressed partial sums through the
all-reduce (``dist/compress.py`` generalized from gradients to
activations, error feedback off — inference has no next iteration to
carry a residual into).  ``wire_bits=32`` is exact up to float summation
order; greedy token-identity vs ``tp=1`` is CI-gated in
``benchmarks/serve_bench.py --tp``.

Trace hygiene: the shard_map bodies call the *unjitted* ``lm`` functions
(``decode_step.__wrapped__`` / ``prefill`` + the raw scatter helpers)
inside ``repro.dist.sharding.tp_context``, so the collective hooks lower
exactly where this module traces them and no inner jit can cache a
collective-free trace against the same avals.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist.sharding import (_NAME_RE, _QUANT_FIELDS, _ROW_PARALLEL,
                                 _trim_spec, tp_context)
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.models.common import ModelConfig
from repro.models.sail_linear import QTensor, StackedQTensor
from repro.planning.cost import tp_allreduce_elems

__all__ = [
    "TPServing", "local_config", "localize_params", "serving_param_spec",
    "shard_alignment_error", "tp_allreduce_elems", "tp_supported",
]

# The seven block matrices TP shards; everything else is replicated so
# every shard holds the full LUT machinery and the full logits path.
_COLUMN_PARALLEL = ("wq", "wk", "wv", "w_gate", "w_up")


def tp_supported(cfg: ModelConfig, tp: int) -> Optional[str]:
    """Why this (config, shard count) cannot serve tensor-parallel —
    ``None`` when it can.

    TP serving covers the dense GQA attention family (the architectures
    the LUT-GEMV decode path itself serves); recurrent state, expert
    routing, and bias-after-reduce layouts are explicitly out of scope
    rather than silently wrong.
    """
    if tp <= 1:
        return None
    if cfg.layer_types is not None:
        return ("layer_types (layers of different attention kinds) are "
                "not tensor-parallel servable: the shard_map entry points "
                "do not carry each layer's window and RoPE")
    if cfg.family != "dense":
        return (f"family={cfg.family!r} is not tensor-parallel servable "
                "(dense attention only — recurrent/expert state does not "
                "shard on the model axis)")
    if cfg.attention_bias or cfg.mlp_bias:
        return ("attention/MLP biases are not supported under TP (bias "
                "addition must move after the partial-sum reduce)")
    if cfg.n_heads % tp:
        return f"n_heads={cfg.n_heads} not divisible by tp={tp}"
    if cfg.n_kv % tp:
        return f"n_kv={cfg.n_kv} not divisible by tp={tp}"
    if cfg.d_ff % tp:
        return f"d_ff={cfg.d_ff} not divisible by tp={tp}"
    return None


def local_config(cfg: ModelConfig, tp: int) -> ModelConfig:
    """The per-shard view of ``cfg``: each shard runs the unchanged model
    code over its own heads and FFN slice.  ``d_head`` is pinned because
    it defaults to ``d_model // n_heads`` and must not change when
    ``n_heads`` shrinks."""
    if tp <= 1:
        return cfg
    return dataclasses.replace(
        cfg, n_heads=cfg.n_heads // tp, n_kv=cfg.n_kv // tp,
        d_ff=cfg.d_ff // tp, d_head=cfg.head_dim)


def serving_param_spec(path: str, shape: Tuple[int, ...]) -> P:
    """PartitionSpec of one serving parameter over the ("data", "model")
    mesh.

    Differs from the training rule (``dist.sharding.param_spec``) where
    serving correctness demands it: embeddings and ``lm_head`` are
    REPLICATED (every shard computes the full logits row, so argmax
    needs no gather), and only the seven block matrices shard.  Quantized
    leaves follow the matrix they encode; codebooks replicate.
    """
    nd = len(shape)
    quant_field = next((f for f in _QUANT_FIELDS if path.endswith(f)), None)
    if quant_field is not None:
        if quant_field == ".codebook":
            return P(*([None] * nd))
        return serving_param_spec(path[: -len(quant_field)], shape)
    names = _NAME_RE.findall(path)
    leaf = names[-1] if names else ""
    spec: list = [None] * nd
    if nd >= 2 and leaf in _ROW_PARALLEL:
        spec[-2] = "model"
    elif nd >= 2 and leaf in _COLUMN_PARALLEL:
        spec[-1] = "model"
    return P(*spec)


def _cache_spec(shape: Tuple[int, ...]) -> P:
    """KV pool arrays shard on the kv-head axis: axis 3 of the ring
    ``[L, B, S, n_kv, Dh]`` (and its ``[.., n_kv, 1]`` scales), and the
    last axis of the lane-dense paged ``[L, NB, BS, n_kv*Dh]`` and its
    ``[L, NB, n_kv*BS]`` scales (heads-major, so an even split is a split
    by head); ``length`` and any other bookkeeping replicate."""
    if len(shape) == 5:
        return P(None, None, None, "model", None)
    if len(shape) in (3, 4):
        return P(*([None] * (len(shape) - 1) + ["model"]))
    return P(*([None] * len(shape)))


def _is_qtensor(x) -> bool:
    return isinstance(x, (QTensor, StackedQTensor))


def localize_params(params, tp: int):
    """Fix up static QTensor metadata for the per-shard view.

    A row-parallel quantized leaf arrives inside the shard_map body with
    its arrays already sliced to the local K range, but ``k`` is static
    metadata carried by the treedef — still the global value.  Rewrite it
    to ``k // tp`` on ``wo``/``w_down`` leaves so ``unpack_grouped`` and
    the kernels see a self-consistent local tensor.  Column-parallel and
    replicated quantized leaves keep their full K and need no change.
    """
    if tp <= 1:
        return params

    def one(key_path, leaf):
        if not _is_qtensor(leaf):
            return leaf
        names = _NAME_RE.findall(jax.tree_util.keystr(key_path))
        if names and names[-1] in _ROW_PARALLEL:
            return dataclasses.replace(leaf, k=leaf.k // tp)
        return leaf

    return jax.tree_util.tree_map_with_path(one, params, is_leaf=_is_qtensor)


def shard_alignment_error(params, tp: int) -> Optional[str]:
    """Why this quantized tree cannot shard ``tp`` ways — ``None`` when
    it can.  Row-parallel quantized leaves split their K dim, so the
    per-shard slice must cover whole quantization groups:
    ``(k // group_size) % tp == 0``."""
    if tp <= 1:
        return None
    problems = []

    def one(key_path, leaf):
        if not _is_qtensor(leaf):
            return
        names = _NAME_RE.findall(jax.tree_util.keystr(key_path))
        if names and names[-1] in _ROW_PARALLEL:
            groups = leaf.k // leaf.group_size
            if groups % tp:
                problems.append(
                    f"{names[-1]}: {groups} quant groups (k={leaf.k}, "
                    f"G={leaf.group_size}) not divisible by tp={tp}")

    jax.tree_util.tree_map_with_path(one, params, is_leaf=_is_qtensor)
    return "; ".join(problems) if problems else None


class TPServing:
    """Sharded drop-in for the ``lm`` serving entry points.

    Owns the ``(1, M)`` mesh, the placement rules, and a memoized family
    of jitted ``shard_map`` wrappers around ``lm.decode_step`` /
    ``lm.prefill`` (+ the pool scatter helpers).  The engine constructs
    one when ``tp > 1``, places its params/cache through
    :meth:`shard_params` / :meth:`shard_cache`, and routes every model
    call here; scheduling, sampling, and block accounting stay logical.
    """

    def __init__(self, cfg: ModelConfig, tp: int, wire_bits: int = 32):
        reason = tp_supported(cfg, tp)
        if reason is not None:
            raise ValueError(f"tensor-parallel serving unavailable: {reason}")
        if wire_bits not in (8, 32):
            raise ValueError(f"wire_bits must be 8 or 32, got {wire_bits}")
        if len(jax.devices()) < tp:
            raise ValueError(
                f"tp={tp} needs {tp} devices but only "
                f"{len(jax.devices())} are visible — on CPU set "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                "before importing jax")
        self.cfg = cfg
        self.tp = int(tp)
        self.wire_bits = int(wire_bits)
        self.mesh = make_mesh((1, self.tp), ("data", "model"))
        self.lcfg = local_config(cfg, self.tp)
        self._fns: Dict[Any, Any] = {}

    # -- placement ---------------------------------------------------------

    def _leaf_spec(self, key_path, leaf, kind: str) -> P:
        shape = tuple(getattr(leaf, "shape", ()))
        if kind == "params":
            spec = serving_param_spec(jax.tree_util.keystr(key_path), shape)
        elif kind == "cache":
            spec = _cache_spec(shape)
        else:
            spec = P(*([None] * len(shape)))
        return _trim_spec(spec, shape, self.mesh)

    def _spec_tree(self, tree, kind: str):
        return jax.tree_util.tree_map_with_path(
            lambda p, l: self._leaf_spec(p, l, kind), tree)

    def _sharding_tree(self, tree, kind: str):
        return jax.tree_util.tree_map_with_path(
            lambda p, l: NamedSharding(self.mesh,
                                       self._leaf_spec(p, l, kind)), tree)

    def shard_params(self, params):
        """Place a (quantized or raw) parameter tree onto the mesh.

        Raises when a row-parallel quantized leaf's group count does not
        divide the shard count — ``_trim_spec`` would silently replicate
        it, and a replicated K-slice under a local-``k`` fixup is wrong,
        not slow."""
        err = shard_alignment_error(params, self.tp)
        if err is not None:
            raise ValueError(
                f"quantized tree cannot shard tp={self.tp} ways: {err} — "
                "use a group_size whose per-matrix group count divides "
                "the shard count")
        return jax.device_put(params, self._sharding_tree(params, "params"))

    def shard_cache(self, cache):
        """Place a KV pool (ring or paged) onto the mesh."""
        return jax.device_put(cache, self._sharding_tree(cache, "cache"))

    def allreduce_bytes_per_iter(self, batch: int) -> int:
        """All-reduce bytes one decode iteration moves per shard (the
        ring all-reduce's 2(M-1)/M factor applied to the payload)."""
        payload = batch * tp_allreduce_elems(self.cfg) * self.wire_bits // 8
        return int(payload * 2 * (self.tp - 1) / self.tp)

    # -- shard_map wrappers ------------------------------------------------

    def _kind_of(self, key_path) -> str:
        names = _NAME_RE.findall(jax.tree_util.keystr(key_path))
        return names[0] if names else ""

    def _build(self, kind: str, arrays: Dict[str, Any], body):
        in_spec = jax.tree_util.tree_map_with_path(
            lambda p, l: self._leaf_spec(
                p, l, {"params": "params", "cache": "cache"}.get(
                    self._kind_of(p), "other")), arrays)
        out_spec = (P(None, None), in_spec["cache"])
        return jax.jit(jax.shard_map(body, mesh=self.mesh,
                                     in_specs=(in_spec,),
                                     out_specs=out_spec, check_vma=False))

    def _get(self, key, arrays, body):
        fn = self._fns.get(key)
        if fn is None:
            fn = self._build(key[0], arrays, body)
            self._fns[key] = fn
        return fn

    def decode_step(self, params, tokens, cache, quant_kv: bool = False,
                    active_mask=None, block_tables=None):
        """One TP decode iteration: (logits [B, V] replicated, sharded
        cache).  Mirrors ``lm.decode_step`` minus the tap capture path
        (taps are gated off under TP)."""
        arrays: Dict[str, Any] = {"params": params, "tokens": tokens,
                                  "cache": cache}
        if active_mask is not None:
            arrays["active_mask"] = active_mask
        if block_tables is not None:
            arrays["block_tables"] = block_tables
        lcfg, tp, wire = self.lcfg, self.tp, self.wire_bits

        def body(a):
            local = localize_params(a["params"], tp)
            with tp_context("model", wire):
                return lm.decode_step.__wrapped__(
                    local, a["tokens"], a["cache"], lcfg,
                    quant_kv=quant_kv,
                    active_mask=a.get("active_mask"),
                    block_tables=a.get("block_tables"))

        key = ("decode", bool(quant_kv), frozenset(arrays))
        return self._get(key, arrays, body)(arrays)

    def prefill_into_slot(self, params, tokens, cache, slots,
                          quant_kv: bool = False, lengths=None):
        """TP slot prefill: prefill under shard_map, scatter the fresh
        (sharded) cache rows into the pool with the raw scatter helper —
        the kv-head axis is untouched by the slot scatter, so the write
        stays shard-local."""
        arrays: Dict[str, Any] = {
            "params": params, "tokens": tokens, "cache": cache,
            "slots": jnp.atleast_1d(jnp.asarray(slots, jnp.int32))}
        if lengths is not None:
            arrays["lengths"] = lengths
        lcfg, tp, wire = self.lcfg, self.tp, self.wire_bits

        def body(a):
            local = localize_params(a["params"], tp)
            cache_len = a["cache"]["layers"]["k"].shape[2]
            with tp_context("model", wire):
                logits, fresh = lm.prefill(
                    local, a["tokens"], lcfg, cache_len=cache_len,
                    quant_kv=quant_kv, lengths=a.get("lengths"))
            return logits, lm._scatter_slots(a["cache"], fresh, a["slots"])

        key = ("prefill_slot", bool(quant_kv), frozenset(arrays))
        return self._get(key, arrays, body)(arrays)

    def prefill_into_blocks(self, params, tokens, cache, slots, phys, offs,
                            quant_kv: bool = False, lengths=None):
        """TP paged prefill: same shape as :meth:`prefill_into_slot`
        with the block-scatter helper; ``phys``/``offs`` destinations are
        logical (block, offset) pairs and replicate."""
        arrays: Dict[str, Any] = {
            "params": params, "tokens": tokens, "cache": cache,
            "slots": jnp.atleast_1d(jnp.asarray(slots, jnp.int32)),
            "phys": jnp.asarray(phys, jnp.int32),
            "offs": jnp.asarray(offs, jnp.int32)}
        if lengths is not None:
            arrays["lengths"] = lengths
        lcfg, tp, wire = self.lcfg, self.tp, self.wire_bits

        def body(a):
            local = localize_params(a["params"], tp)
            with tp_context("model", wire):
                logits, fresh = lm.prefill(
                    local, a["tokens"], lcfg,
                    cache_len=a["tokens"].shape[1],
                    quant_kv=quant_kv, lengths=a.get("lengths"))
            return logits, lm._scatter_blocks(a["cache"], fresh, a["slots"],
                                              a["phys"], a["offs"])

        key = ("prefill_blocks", bool(quant_kv), frozenset(arrays))
        return self._get(key, arrays, body)(arrays)
