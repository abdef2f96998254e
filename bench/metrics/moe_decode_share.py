"""Share of the decode step program's device op time spent in ops of the
``moe`` scope (router, sort, grouped expert matmuls, combine); the rest
is ``sail_mm``, ``attn`` and other ops.  A faster expert layer lowers
it."""
UNIT = "%"
LAYER = "decode step program"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(ctx):
    scopes = (ctx.get("trace") or {}).get("scope_s")
    if not scopes or "moe" not in scopes:
        return None
    return scopes["moe"] / sum(scopes.values()) * 100.0
