"""x64-off context for the Pallas kernels.

paddle_tpu enables x64 globally (paddle int64/float64 semantics), but
the Pallas kernels must trace with x64 semantics disabled so weak
python constants stay 32-bit — Mosaic rejects 64-bit avals.
`jax.enable_x64(False)` is the trace-safe context manager for that.
"""
from __future__ import annotations

import jax

__all__ = ["x64_off"]


def x64_off():
    return jax.enable_x64(False)
