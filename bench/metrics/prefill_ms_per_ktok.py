"""Host milliseconds per thousand prompt tokens: each engine step that
ran a prefill, less the decode time the engine counted in it, over the
prompt tokens prefilled."""
UNIT = "ms/ktok"
LAYER = "model step, prefill"
SOURCE = "host_clock"
MOVES = "output_tok_s"


def read(ctx):
    tokens = ctx["counters"]["prefill_tokens"]
    if not tokens:
        return None
    return ctx["prefill_host_s"] / tokens * 1e6
