"""Share of the traced window in which the device was idle while the
host sat blocked in ``engine.sample``, waiting for the device: idle time
the device programs themselves leave."""
UNIT = "%"
LAYER = "decode step program"
SOURCE = "device_trace"
MOVES = "output_tok_s"
WAIT = "engine.sample"


def read(ctx):
    tr = ctx["trace"]
    idle = (tr or {}).get("idle_by_span")
    if not idle or tr["window_s"] <= 0 or set(idle) == {"none"}:
        return None
    return idle.get(WAIT, 0.0) / tr["window_s"] * 100.0
