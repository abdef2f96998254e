"""Host milliseconds per decode iteration as the engine times it (from
the decode call through the blocking sample)."""
UNIT = "ms"
LAYER = "model step, decode"
SOURCE = "program_span"
MOVES = "output_tok_s"


def read(ctx):
    c = ctx["counters"]
    if not c["decode_iterations"]:
        return None
    return c["decode_seconds"] / c["decode_iterations"] * 1e3
