"""Operations and bytes the algorithm needs, from shapes and precision.

These are the counts of the work itself, whatever implements it: a
quantized matrix costs its packed codes plus its float32 group scales
(and its small codebook), a float32 matrix four bytes a weight, and the
KV cache only the positions each lane attends to.  A faster matmul path
moves the time these counts are divided by, never the counts.

``m`` is a configuration's ``model`` dict; ``prec`` is a cell's
``precision`` dict (``weight_bits`` or null, ``group_size``,
``kv_bits``).  These are the counts of a dense decoder; a configuration
of another kind names its own module (``bench/run.py``).
"""
from __future__ import annotations

from typing import Dict, Iterable, Sequence, Tuple


def head_dim(m: Dict) -> int:
    return m["d_head"] if m.get("d_head") else m["d_model"] // m["n_heads"]


def layer_matmuls(m: Dict) -> Dict[str, Tuple[int, int]]:
    """``(K, N)`` of each weight matrix of one decoder layer."""
    d, f, hd = m["d_model"], m["d_ff"], head_dim(m)
    q, kv = m["n_heads"] * hd, m["n_kv"] * hd
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def block_weights(m: Dict) -> int:
    """Matmul weights of all decoder layers."""
    return m["n_layers"] * sum(k * n for k, n in layer_matmuls(m).values())


def head_weights(m: Dict) -> int:
    return m["d_model"] * m["vocab"]


def matmul_weights(m: Dict) -> int:
    """Matmul weights one decoded token passes through."""
    return block_weights(m) + head_weights(m)


def matrix_bytes(k: int, n: int, prec: Dict) -> int:
    bits = prec.get("weight_bits")
    if not bits:
        return 4 * k * n
    g = prec["group_size"]
    words = -(-bits * g // 32)              # per group and column
    return (k // g) * n * (4 * words + 4) + 4 * (1 << bits)


def weight_bytes(m: Dict, prec: Dict) -> int:
    """Bytes of every matmul weight, read once per decode step."""
    per_layer = sum(matrix_bytes(k, n, prec)
                    for k, n in layer_matmuls(m).values())
    return (m["n_layers"] * per_layer
            + matrix_bytes(m["d_model"], m["vocab"], prec))


def kv_bytes_per_position(m: Dict, prec: Dict) -> int:
    """Keys and values of one position over all layers (int8 codes plus a
    float32 scale per KV head, or float32)."""
    per_head = (head_dim(m) + 4) if prec["kv_bits"] == 8 else 4 * head_dim(m)
    return m["n_layers"] * 2 * m["n_kv"] * per_head


def attention_flops(m: Dict, pairs: int) -> int:
    """Scores and weighted values over ``pairs`` (query, key) pairs in
    every layer."""
    return m["n_layers"] * 4 * m["n_heads"] * head_dim(m) * pairs


def decode_step(m: Dict, prec: Dict, contexts: Iterable[int]) -> Tuple[int, int]:
    """(FLOPs, bytes) of one decode step whose active lanes attend to
    ``contexts`` positions each (their own new position included)."""
    ctx = list(contexts)
    flops = (2 * matmul_weights(m) * len(ctx)
             + attention_flops(m, sum(ctx)))
    kvp = kv_bytes_per_position(m, prec)
    byts = (weight_bytes(m, prec)
            + sum(ctx) * kvp                 # read what each lane attends
            + len(ctx) * (kvp + 4 * m["d_model"]))   # write, embed row
    return flops, byts


def prefill_flops(m: Dict, prompt_len: int) -> int:
    """FLOPs of one prompt: every position through the layers, causal
    attention, and the output head at the last position only."""
    p = prompt_len
    return (2 * block_weights(m) * p + 2 * head_weights(m)
            + attention_flops(m, p * (p + 1) // 2))


def decode_scopes(m: Dict, prec: Dict, steps: Sequence[Sequence[int]],
                  stats: Dict) -> Dict[str, Tuple[int, int]]:
    """(FLOPs, bytes) of the window's decode steps, each step given by
    the contexts of its lanes, split by the scopes the program opens:
    ``sail_mm``, every matmul a step runs, the head included (its weights
    read once a step); ``attn``, attention over the positions each lane
    attends to, whose KV it reads, and the KV it writes.  A dense model
    needs none of the window's ``stats``."""
    steps = [c for c in steps if c]
    lanes = sum(len(c) for c in steps)
    pairs = sum(sum(c) for c in steps)
    kvp = kv_bytes_per_position(m, prec)
    return {"sail_mm": (2 * matmul_weights(m) * lanes,
                        len(steps) * weight_bytes(m, prec)),
            "attn": (attention_flops(m, pairs), (pairs + lanes) * kvp)}
