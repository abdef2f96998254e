"""Pallas TPU kernels for the paper's hot spots (LUT-GEMV, typeconv) and
for paged decode attention.

Backend selection for the LUT-GEMV and typeconv kernels lives here: a
kernel compiles for the TPU when one is attached and runs the Pallas
interpreter otherwise.  The decode attention kernel is lowered only for a
TPU (``lax.platform_dependent`` in ``repro.models.blocks``); its tests
run it interpreted.
"""
from __future__ import annotations

import jax


def default_interpret() -> bool:
    """Interpret Pallas kernels only when no TPU is attached.

    The one place the interpreter is chosen, so a TPU run never silently
    executes it.
    """
    return jax.default_backend() != "tpu"
