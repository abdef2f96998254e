"""Share of the traced window in which the device was idle while the
host's innermost open span was an ``engine.*`` span other than
``engine.sample``: admission, prefill set-up and its compiles, commits,
the KV grant, decode dispatch."""
UNIT = "%"
LAYER = "engine host loop"
SOURCE = "device_trace"
MOVES = "output_tok_s"
WAIT = "engine.sample"


def read(ctx):
    tr = ctx["trace"]
    idle = (tr or {}).get("idle_by_span")
    if not idle or tr["window_s"] <= 0 or set(idle) == {"none"}:
        return None
    work = sum(v for k, v in idle.items() if k not in ("none", WAIT))
    return work / tr["window_s"] * 100.0
