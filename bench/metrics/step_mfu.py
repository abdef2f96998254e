"""Model FLOPs of every prompt whose first token and every token decoded
in the traced window, over the window's seconds times the chip's bf16
peak."""
UNIT = "%"
LAYER = "whole step"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["window_flops"]:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return ctx["window_flops"] / (tr["window_s"] * peak) * 100.0
