"""Public op: EmbeddingBag with kernel/oracle dispatch.

Accepts (B, L) padded bags (padding = -1) like torch's EmbeddingBag with
offsets; flattens, drops padding, sorts by bag, and dispatches to the
scalar-prefetch kernel or the jnp oracle.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..interpret import resolve_interpret
from .kernel import embedding_bag
from .ref import embedding_bag_ref


def embedding_bag_op(
    table: jnp.ndarray,  # (V, D)
    bags: jnp.ndarray,  # (B, L) int32, padded with -1
    mode: str = "sum",
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    b, l = bags.shape
    flat = bags.reshape(-1)
    segments = jnp.repeat(jnp.arange(b, dtype=jnp.int32), l)
    valid = flat >= 0
    # route padding to row 0 with weight 0 via a zero row appended to the
    # table (static shapes: we cannot drop entries)
    v, d = table.shape
    table_ext = jnp.concatenate([table, jnp.zeros((1, d), table.dtype)], axis=0)
    idx = jnp.where(valid, flat, v)
    if use_kernel:
        out = embedding_bag(
            table_ext, idx, segments, n_bags=b,
            interpret=resolve_interpret(interpret),
        )
    else:
        out = embedding_bag_ref(table_ext, idx, segments, n_bags=b)
    if mode == "mean":
        cnt = valid.reshape(b, l).sum(axis=1).astype(table.dtype)
        out = out / jnp.maximum(cnt, 1.0)[:, None]
    return out
