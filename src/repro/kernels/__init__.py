"""Pallas kernels of the packed mixed-precision path.

Every ``pallas_call`` in this package takes its ``interpret`` flag from
:func:`interpret_mode`, the one place that decides whether a kernel is
compiled by Mosaic or run by the Pallas interpreter.
"""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Whether a kernel traced now runs in the Pallas interpreter.

    ``None`` (what every caller passes by default) is decided when the
    kernel is traced, from the default backend: compiled on a TPU,
    interpreted everywhere else.  An explicit bool wins: the v5e compile
    tests pass ``False`` to lower for a described chip from a CPU process.
    """
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"
