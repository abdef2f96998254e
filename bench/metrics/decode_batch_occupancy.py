"""Mean lanes per decode step: the engine's decoded tokens over its
decode iterations in the window."""
UNIT = "lanes"
LAYER = "scheduler and paged KV pool"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(ctx):
    c = ctx["counters"]
    if not c["decode_iterations"]:
        return None
    return c["decode_tokens"] / c["decode_iterations"]
