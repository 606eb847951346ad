"""The one switch between Mosaic compilation and the Pallas interpreter."""

from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True off-TPU: every kernel in this package passes this to
    ``pallas_call(interpret=...)``, so on a TPU backend a kernel is always
    compiled by Mosaic and never silently interpreted."""
    return jax.default_backend() != "tpu"
