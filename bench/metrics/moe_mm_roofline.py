"""Share of its roofline reached by the ``moe`` scope of the decode step
program: the float32 router, the sort of (token, expert) pairs, the
grouped matmuls of the routed experts and the combine.  Its work is
``bench/work/moe_swa_gqa.py``'s (2·3·d·f FLOPs a routed pair; each
expert touched read once a step, as the engine's ``moe.*`` counters
count them) over the device time of the scope's ops."""
from bench.roofline import scope_share

UNIT = "%"
LAYER = "routed experts"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(ctx):
    return scope_share(ctx, "moe")
