"""Share of the decode step program's device op time spent in ops of the
``sail_mm`` scope (the LUT dequant and dot of every matmul and the
head); the rest is ``attn`` and other ops.  A faster matmul lowers it."""
UNIT = "%"
LAYER = "decode step program"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(ctx):
    scopes = (ctx["trace"] or {}).get("scope_s")
    if not scopes or not ({"sail_mm", "attn"} & set(scopes)):
        return None
    return scopes.get("sail_mm", 0.0) / sum(scopes.values()) * 100.0
