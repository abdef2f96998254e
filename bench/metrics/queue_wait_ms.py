"""Mean milliseconds a request waited between ``submit`` and its first
prefill, over the requests first admitted in the window."""
UNIT = "ms"
LAYER = "scheduler and paged KV pool"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(ctx):
    q = ctx.get("queue")
    if not q or not q["admissions"]:
        return None
    return q["wait_s"] / q["admissions"] * 1e3
