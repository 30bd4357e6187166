"""Kernel micro-benchmarks: Pallas kernel vs jnp oracle agreement plus
oracle timing.  The kernels compile on an accelerator and run in the
Pallas interpreter on CPU hosts (``repro.kernels.interpret``); each row's
timing is for whatever backend ran it (the ``meta/run`` row names it)."""
from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import decode_attention_op, embedding_bag_op, topic_score_op
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.topic_score.ref import topic_score_ref

from .common import csv_row


def run() -> List[str]:
    rows: List[str] = []
    rng = np.random.default_rng(0)

    # topic_score: oracle throughput + kernel agreement
    b, v, k = 512, 2048, 500
    counts = jnp.asarray(rng.poisson(0.02, size=(b, v)).astype(np.float32))
    counts = counts.at[:, 0].set(1.0)
    phi = jnp.asarray(
        np.log(rng.dirichlet(np.ones(v) * 0.1, size=k).T + 1e-12).astype(np.float32)
    )
    ref = jax.jit(topic_score_ref)
    ref(counts, phi)[0].block_until_ready()
    t0 = time.time()
    for _ in range(10):
        s0, t0s, c0 = ref(counts, phi)
    s0.block_until_ready()
    us = (time.time() - t0) / 10 * 1e6
    s1, t1, c1 = topic_score_op(counts, phi, use_kernel=True)
    agree = float((t1 == t0s).mean())
    rows.append(
        csv_row(f"perf/topic_score/B={b}xV={v}xK={k}", us, f"kernel_top_agree={agree:.4f}")
    )

    # embedding_bag
    table = jnp.asarray(rng.normal(size=(10_000, 128)).astype(np.float32))
    bags = jnp.asarray(rng.integers(-1, 10_000, size=(256, 16)).astype(np.int32))
    ref_fn = jax.jit(lambda t, b: embedding_bag_op(t, b, use_kernel=False))
    ref_fn(table, bags).block_until_ready()
    t0 = time.time()
    for _ in range(20):
        out0 = ref_fn(table, bags)
    out0.block_until_ready()
    us = (time.time() - t0) / 20 * 1e6
    out1 = embedding_bag_op(table, bags, use_kernel=True)
    err = float(jnp.abs(out1 - out0).max())
    rows.append(csv_row("perf/embedding_bag/B=256xL=16xD=128", us, f"kernel_err={err:.1e}"))

    # decode attention
    q = jnp.asarray(rng.normal(size=(4, 4, 4, 128)).astype(np.float32))
    kk = jnp.asarray(rng.normal(size=(4, 2048, 4, 128)).astype(np.float32))
    vv = jnp.asarray(rng.normal(size=(4, 2048, 4, 128)).astype(np.float32))
    ref_fn = jax.jit(lambda q, k, v: decode_attention_ref(q, k, v, jnp.asarray(2000), 128**-0.5))
    ref_fn(q, kk, vv).block_until_ready()
    t0 = time.time()
    for _ in range(20):
        o0 = ref_fn(q, kk, vv)
    o0.block_until_ready()
    us = (time.time() - t0) / 20 * 1e6
    o1 = decode_attention_op(q, kk, vv, 2000, scale=128**-0.5, use_kernel=True)
    err = float(jnp.abs(o1 - o0).max())
    rows.append(csv_row("perf/decode_attention/B4xS2048", us, f"kernel_err={err:.1e}"))
    return rows
