"""Share of the roofline reached by the decode step program: the least
time the chip could take for the window's decode steps (the larger of
their FLOPs over peak FLOP/s and their bytes over peak bandwidth, from
``bench/counts.py``) over the device time of trace modules whose name
holds ``decode_step``."""
UNIT = "%"
LAYER = "decode step program"
SOURCE = "device_trace"
MOVES = "output_tok_s"
MODULE = "decode_step"


def bound(ctx):
    """(seconds, 'flops' or 'bytes'): the least time and what sets it."""
    p = ctx["peaks"]
    t_f = ctx["decode_flops"] / p["bf16_flops_per_s"]
    t_b = ctx["decode_bytes"] / p["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f > t_b else (t_b, "bytes")


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["decode_bytes"]:
        return None
    t = sum(v for k, v in tr["modules"].items() if MODULE in k)
    if t <= 0:
        return None
    return bound(ctx)[0] / t * 100.0
