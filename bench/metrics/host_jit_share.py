"""Share of the window the host spent tracing, lowering and compiling
programs (JAX's compile events, the overlap of nested ones counted once),
in every span and outside them."""
UNIT = "%"
LAYER = "host JIT"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(ctx):
    spans = ctx.get("spans")
    if spans is None or ctx["window_s"] <= 0:
        return None
    jit = sum(s["jit_s"] for s in spans.values())
    return jit / ctx["window_s"] * 100.0
