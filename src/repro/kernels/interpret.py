"""Pallas interpret mode, decided from the platform in one place.

Every kernel op takes ``interpret=None`` and resolves it here: the Pallas
interpreter on CPU hosts (which have no Mosaic backend), the compiled
kernel on an accelerator.  An explicit ``True``/``False`` from the caller
wins, so CPU tests can pin the interpreter and nothing on a chip runs a
kernel in the interpreter unless it asked to.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
