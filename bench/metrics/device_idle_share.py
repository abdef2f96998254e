"""Share of the traced window in which no operation ran on the device."""
UNIT = "%"
LAYER = "device"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
