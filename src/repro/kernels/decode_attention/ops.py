"""Public op: GQA decode attention with kernel/oracle dispatch."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ..interpret import resolve_interpret
from .kernel import decode_attention
from .ref import decode_attention_ref


def decode_attention_op(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cur_len,
    scale: float,
    softcap: Optional[float] = None,
    window: Optional[int] = None,
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    if not use_kernel:
        return decode_attention_ref(q, k, v, cur_len, scale, softcap, window)
    # pad the cache length to a block multiple (padded keys are masked out
    # by the validity predicate; padded values are zeros so 0*0 stays 0)
    s = k.shape[1]
    bs = min(512, s)
    pad = (-s) % bs
    if pad:
        cfg = [(0, 0), (0, pad), (0, 0), (0, 0)]
        k = jnp.pad(k, cfg)
        v = jnp.pad(v, cfg)
    return decode_attention(
        q, k, v, cur_len, scale=scale, softcap=softcap, window=window, bs=bs,
        interpret=resolve_interpret(interpret),
    )
