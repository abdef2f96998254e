"""Share of its roofline reached by the ``attn`` scope of the decode step
program: the KV write, the paged gather, the int8 dequant and attention.
Its work is ``bench/counts.py``'s (scores and weighted values over the
positions each lane attends to; the KV of those positions read, and of
the new one written) over the device time of the scope's ops."""
from bench.roofline import scope_share

UNIT = "%"
LAYER = "decode attention"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(ctx):
    return scope_share(ctx, "attn")
