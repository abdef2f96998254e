"""A named scope's share of its roofline, for the readers of kernels.

The least time the chip could take for the scope's work in the window's
decode steps (``ctx["scope_work"]``, from the configuration's counts) is
the larger of its FLOPs over the bf16 peak and its bytes over HBM
bandwidth; the share is that over the device time of the scope's ops
(``ctx["trace"]["scope_s"]``).
"""
from __future__ import annotations

from typing import Dict, Optional


def scope_share(ctx: Dict, scope: str) -> Optional[float]:
    """Percent of the roofline the scope's ops reached; None where the
    counts give the scope no work or the trace no time."""
    work = (ctx.get("scope_work") or {}).get(scope)
    seconds = ((ctx.get("trace") or {}).get("scope_s") or {}).get(scope, 0.0)
    if not work or not any(work) or seconds <= 0:
        return None
    flops, byts = work
    p = ctx["peaks"]
    least = max(flops / p["bf16_flops_per_s"], byts / p["hbm_bytes_per_s"])
    return least / seconds * 100.0
