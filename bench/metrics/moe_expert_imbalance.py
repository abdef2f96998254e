"""How unevenly a decode step's routed (token, expert) pairs fall on the
experts they touch: the largest expert's tokens over the mean tokens per
touched expert, averaged over the window's layer-steps, from the
engine's ``moe.imbalance_sum`` and ``moe.layer_steps`` counters.  A
grouped matmul waits on its largest group, so a higher ratio wastes
more of the expert layer's time."""
UNIT = "ratio"
LAYER = "routed experts"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(ctx):
    stats = ctx.get("stats") or {}
    steps = stats.get("moe.layer_steps")
    if not steps:
        return None
    return stats["moe.imbalance_sum"] / steps
