"""Public op: topic scoring with kernel/oracle dispatch.

``topic_score_op`` pads inputs to MXU-aligned shapes, invokes the Pallas
kernel (the interpreter on CPU hosts), and un-pads.  ``use_kernel=False``
routes to the pure-jnp oracle -- the serving pipeline flips this on TPU.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..interpret import resolve_interpret
from .kernel import topic_score
from .ref import topic_score_ref


def _pad_to(x: jnp.ndarray, axis: int, mult: int, value=0.0):
    size = x.shape[axis]
    target = ((size + mult - 1) // mult) * mult
    if target == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(x, pad, constant_values=value)


def topic_score_op(
    counts: jnp.ndarray,
    log_phi_t: jnp.ndarray,
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
):
    """counts (B, V) f32, log_phi_t (V, K) f32 ->
    (scores (B, K), top (B,) int32, conf (B,) f32)."""
    if not use_kernel:
        return topic_score_ref(counts, log_phi_t)
    b, v = counts.shape
    k = log_phi_t.shape[1]
    # pad to full grid blocks (bm=256, bv=512): out-of-bounds block reads
    # are undefined in Pallas, so shapes must tile exactly
    counts_p = _pad_to(_pad_to(counts, 0, 256), 1, 512)
    # padded topics must never win the argmax: give them -inf-ish columns
    phi_p = _pad_to(_pad_to(log_phi_t, 0, 512), 1, 128, value=0.0)
    if phi_p.shape[1] != k:
        neg = jnp.full((phi_p.shape[0], phi_p.shape[1] - k), -1e9, jnp.float32)
        phi_p = jnp.concatenate([phi_p[:, :k], neg], axis=1)
    scores, top, conf = topic_score(
        counts_p, phi_p, interpret=resolve_interpret(interpret)
    )
    # all-zero count rows are degenerate (uniform scores): clamp into range
    top = jnp.minimum(top, k - 1)
    return scores[:b, :k], top[:b], conf[:b]
