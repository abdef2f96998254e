"""The benchmark's operation and byte counts against hand counts."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import counts, run  # noqa: E402

TINY = json.loads((ROOT / "bench/configs/tinymistral_248m.json").read_text())[
    "model"]
Q4 = {"weight_bits": 4, "group_size": 128, "kv_bits": 8}
F32 = {"weight_bits": None, "group_size": 128, "kv_bits": 8}


def test_tinymistral_matmul_weights_per_token():
    # 12 layers x (q, o: 1024x1024; k, v: 1024x256; gate, up, down:
    # 1024x4096) plus the 1024 x 32005 output head: about 216 M
    per_layer = 2 * 1024 * 1024 + 2 * 1024 * 256 + 3 * 1024 * 4096
    assert counts.matmul_weights(TINY) == 12 * per_layer + 1024 * 32005
    assert counts.matmul_weights(TINY) == 215_225_344


def test_quantized_bytes_are_codes_plus_group_scales():
    # 4 bits x 128 rows = 16 words a group and column, plus one f32 scale
    assert counts.matrix_bytes(256, 3, Q4) == 2 * 3 * (16 * 4 + 4) + 4 * 16
    assert counts.matrix_bytes(256, 3, F32) == 4 * 256 * 3
    w4 = counts.weight_bytes(TINY, Q4)
    assert w4 == pytest.approx(counts.matmul_weights(TINY) * (0.5 + 4 / 128),
                               rel=1e-4)
    assert counts.weight_bytes(TINY, F32) == 4 * counts.matmul_weights(TINY)


def test_kv_counts_only_attended_positions():
    kvp = counts.kv_bytes_per_position(TINY, Q4)
    assert kvp == 12 * 2 * 8 * (32 + 4)      # head_dim 1024 / 32
    f0, b0 = counts.decode_step(TINY, Q4, [])
    f1, b1 = counts.decode_step(TINY, Q4, [100, 300])
    assert (f0, b0) == (0, counts.weight_bytes(TINY, Q4))
    assert b1 - b0 == 400 * kvp + 2 * (kvp + 4 * 1024)
    assert f1 == 2 * 2 * 215_225_344 + 12 * 4 * 32 * 32 * 400


def test_prefill_counts_causal_pairs_and_one_head_row():
    f = counts.prefill_flops(TINY, 10)
    blocks = counts.matmul_weights(TINY) - 1024 * 32005
    assert f == 2 * blocks * 10 + 2 * 1024 * 32005 + 12 * 4 * 32 * 32 * 55


def test_a_config_without_counts_gets_the_dense_counts_unchanged():
    # tinymistral_248m's file names no counts module: the window's
    # decode_flops, decode_bytes and window_flops are bench/counts.py's,
    # pinned here as they read before configurations could bring counts
    cell = run.load_cell("tinymistral.decode.q4a8")
    assert "counts" not in cell["config"]
    work = run.counts_for(cell)
    assert work is counts
    m, prec = cell["config"]["model"], cell["traffic"]["precision"]
    steps = [[300, 1000, 64], [301, 1001]]
    dec = [work.decode_step(m, prec, c) for c in steps]
    assert sum(f for f, _ in dec) == 2_283_292_672
    assert sum(b for _, b in dec) == 247_170_240
    assert (sum(f for f, _ in dec) + work.prefill_flops(m, 256)
            + work.prefill_flops(m, 1000)) == 486_951_852_032
    # the split by scope covers the step's work: all of its FLOPs, and
    # its bytes but for the embedding rows
    split = work.decode_scopes(m, prec, steps + [[]], {})
    assert split == {"sail_mm": (2_152_253_440, 2 * counts.weight_bytes(m, prec)),
                     "attn": (131_039_232, (2666 + 5) * 6912)}
    assert sum(f for f, _ in split.values()) == sum(f for f, _ in dec)
    assert (sum(b for _, b in split.values()) + 5 * 4 * 1024
            == sum(b for _, b in dec))
