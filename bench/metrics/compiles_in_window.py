"""Programs XLA compiled inside the window (a shape neither the process
nor the persistent cache had), from JAX's compile events."""
UNIT = "count"
LAYER = "host JIT"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(ctx):
    return ctx["compiles"]
