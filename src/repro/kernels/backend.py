"""Where the Pallas kernels run: lowered for the TPU, or interpreted.

Every kernel asks :func:`interpret` at trace time, so a test that
compiles a kernel for a described (not attached) TPU steers this one
function instead of each kernel's own backend probe.
"""
from __future__ import annotations

import jax


def interpret() -> bool:
    """True off-TPU: kernels then run through the Pallas interpreter
    (CPU tests and rehearsals); on a TPU backend they lower to Mosaic."""
    return jax.default_backend() != "tpu"
