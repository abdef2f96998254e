"""Operations and bytes of a decoder of sparse-expert layers whose
attention mixes sliding-window and full layers (Mellum2-12B-A2.5B).

As ``bench/counts.py`` counts a dense decoder: the work itself, whatever
implements it.  A token passes through its layers' attention
projections, the float32 router and its ``top_k`` experts (three
matrices each), and the output head.  A sliding layer attends to at
most ``window`` positions, a full layer to the whole context.  A
quantized matrix costs its packed codes, group scales and codebook, read
once a step; the router its float32 weights.  Only experts that some
token reaches are read: ``decode_scopes`` takes how many from the
engine's ``moe.*`` counters, ``decode_step`` (which has no counters)
from the expectation under uniform routing.
"""
from __future__ import annotations

from typing import Dict, Iterable, Sequence, Tuple

from bench import counts


def _dims(m: Dict):
    hd = counts.head_dim(m)
    return (m["d_model"], m["moe_ffn"] or m["d_ff"], m["n_experts"],
            m["top_k"], hd, m["n_heads"] * hd, m["n_kv"] * hd)


def attn_matmuls(m: Dict) -> Dict[str, Tuple[int, int]]:
    d, _, _, _, _, q, kv = _dims(m)
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}


def expert_bytes(m: Dict, prec: Dict) -> int:
    """One expert's three matrices."""
    d, f = _dims(m)[:2]
    return 2 * counts.matrix_bytes(d, f, prec) + counts.matrix_bytes(
        f, d, prec)


def kinds(m: Dict) -> list:
    period = m.get("layer_types") or ["full"]
    return [period[i % len(period)] for i in range(m["n_layers"])]


def attended(m: Dict, ctx: int) -> int:
    """(query, key) pairs of one lane at context ``ctx`` over all layers."""
    return sum(min(ctx, m["window"]) if k == "sliding" else ctx
               for k in kinds(m))


def _attention_flops(m: Dict, pairs: int) -> int:
    """Scores and weighted values over ``pairs`` pairs (summed over
    layers already)."""
    return 4 * m["n_heads"] * counts.head_dim(m) * pairs


def _kv_bytes_per_layer(m: Dict, prec: Dict) -> int:
    return counts.kv_bytes_per_position(dict(m, n_layers=1), prec)


def _projection_weights(m: Dict) -> int:
    """Attention projections of every layer and the head: the matmuls
    of the ``sail_mm`` scope."""
    return (m["n_layers"] * sum(k * n for k, n in attn_matmuls(m).values())
            + m["d_model"] * m["vocab"])


def _projection_bytes(m: Dict, prec: Dict) -> int:
    return (m["n_layers"] * sum(counts.matrix_bytes(k, n, prec)
                                for k, n in attn_matmuls(m).values())
            + counts.matrix_bytes(m["d_model"], m["vocab"], prec))


def _routed_flops_per_token(m: Dict) -> int:
    """Router and the token's experts, one layer."""
    d, f, e, k = _dims(m)[:4]
    return 2 * d * e + k * 2 * 3 * d * f


def decode_step(m: Dict, prec: Dict, contexts: Iterable[int]) -> Tuple[int, int]:
    """(FLOPs, bytes) of one decode step whose active lanes attend to
    ``contexts`` positions each (their own new position included)."""
    ctx = list(contexts)
    d, _, e, k = _dims(m)[:4]
    L, lanes = m["n_layers"], len(ctx)
    flops = (2 * _projection_weights(m) * lanes
             + L * _routed_flops_per_token(m) * lanes
             + _attention_flops(m, sum(attended(m, c) for c in ctx)))
    touched = e * (1 - (1 - k / e) ** lanes) if lanes else 0.0
    kvl = _kv_bytes_per_layer(m, prec)
    byts = (_projection_bytes(m, prec)
            + L * (4 * d * e + touched * expert_bytes(m, prec))
            + sum(attended(m, c) for c in ctx) * kvl
            + lanes * (L * kvl + 4 * d))
    return flops, int(byts)


def prefill_flops(m: Dict, prompt_len: int) -> int:
    """FLOPs of one prompt: every position through the layers, attention
    within each layer's reach, the output head at the last position."""
    p = prompt_len
    per_pos = (2 * sum(k * n for k, n in attn_matmuls(m).values())
               + _routed_flops_per_token(m))
    pairs = sum(attended(m, t + 1) for t in range(p))
    return (m["n_layers"] * per_pos * p + 2 * m["d_model"] * m["vocab"]
            + _attention_flops(m, pairs))


def decode_scopes(m: Dict, prec: Dict, steps: Sequence[Sequence[int]],
                  stats: Dict) -> Dict[str, Tuple[int, int]]:
    """(FLOPs, bytes) of the window's decode steps by the scopes the
    program opens: ``moe``, the router over every active lane and layer
    (its float32 weights read once a layer-step) and 2·3·d·f FLOPs per
    routed (token, expert) pair, the packed codes, scales and codebook
    of each expert touched read once (``moe.routed_pairs`` and
    ``moe.experts_touched`` of the engine's counters); ``sail_mm``, the
    attention projections and the head; ``attn``, attention over the
    positions each lane's layers reach, whose int8 KV it reads, and the
    KV it writes.  Without the counters ``moe`` is left out."""
    steps = [c for c in steps if c]
    d, f, e = _dims(m)[:3]
    L = m["n_layers"]
    lanes = sum(len(c) for c in steps)
    pairs = sum(attended(m, x) for c in steps for x in c)
    kvl = _kv_bytes_per_layer(m, prec)
    out = {"sail_mm": (2 * _projection_weights(m) * lanes,
                       len(steps) * _projection_bytes(m, prec)),
           "attn": (_attention_flops(m, pairs),
                    (pairs + lanes * L) * kvl)}
    routed = (stats or {}).get("moe.routed_pairs")
    touched = (stats or {}).get("moe.experts_touched")
    if routed is not None and touched is not None:
        out["moe"] = (int(2 * d * e * lanes * L + 2 * 3 * d * f * routed),
                      int(4 * d * e * L * len(steps)
                          + touched * expert_bytes(m, prec)))
    return out
