"""Milliseconds per thousand prompt tokens inside the engine's own
``engine.prefill`` spans (prefill, its sample and the KV scatter, without
admission, commits or decode) over the prompt tokens prefilled."""
UNIT = "ms/ktok"
LAYER = "model step, prefill"
SOURCE = "program_span"
MOVES = "output_tok_s"


def read(ctx):
    spans = ctx.get("spans")
    tokens = ctx["counters"]["prefill_tokens"]
    if not spans or "engine.prefill" not in spans or not tokens:
        return None
    return spans["engine.prefill"]["s"] / tokens * 1e6
