"""Peak device memory in use over the run, read after the window."""
UNIT = "GB"
LAYER = "device memory"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(ctx):
    b = ctx["memory_peak_bytes"]
    return b / 1e9 if b else None
