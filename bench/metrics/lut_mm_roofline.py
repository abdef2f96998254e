"""Share of its roofline reached by the ``sail_mm`` scope of the decode
step program: every LUT matmul a decode step runs, the head included.
Its work is ``bench/counts.py``'s (2 FLOPs a weight a lane; each
matrix's packed codes, group scales and codebook read once a step) over
the device time of the scope's ops."""
from bench.roofline import scope_share

UNIT = "%"
LAYER = "LUT matmul"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(ctx):
    return scope_share(ctx, "sail_mm")
