"""Roofline table renderer + fused-serve block-shape autotuner.

Rendering (default): reads dryrun_results.json into EXPERIMENTS.md
markdown (per (arch x shape x mesh): three terms, dominant bottleneck,
useful-compute ratio, roofline fraction, and the what-would-help note).

Autotuning (``--autotune``): sweeps the fused serve kernel's
request-tile size ``bm`` over each serving bucket, records every
shape's us/call and achieved fraction of a *measured* device-copy
roofline (not a datasheet number), and persists the per-(backend,
bucket) winners through :mod:`repro.serving.autotune` so the broker
picks them up at bind time.  On CPU hosts the kernel runs in interpret
mode -- the absolute numbers are then only self-relative, but the sweep
machinery, table schema, and broker pickup are identical to a real
accelerator run.
"""
from __future__ import annotations

import json
import sys
import time
from typing import List


def _advice(r: dict) -> str:
    rf = r["roofline"]
    dom = rf["dominant"]
    kind = r["kind"]
    if dom == "compute":
        if rf["useful_flops_ratio"] < 0.5:
            return "cut recompute/padding waste (remat policy, MoE capacity)"
        return "near compute bound; only faithful-flops wins remain"
    if dom == "memory":
        if kind == "decode":
            return "KV-cache bytes dominate: quantize KV / window local layers"
        if kind == "train":
            return "activation traffic: seq-sharded residual + smaller q-chunk"
        return "stream larger fused blocks; bf16 intermediates"
    if dom == "collective":
        return "overlap or shrink collectives (reduce-scatter grads, fewer all-gathers)"
    return "-"


def render(path: str = "dryrun_results.json") -> List[str]:
    rows = json.load(open(path))
    out = []
    out.append(
        "| arch | shape | mesh | GiB/dev | t_comp (s) | t_mem (s) | t_coll (s) "
        "| bound | useful | roofline frac | next lever |"
    )
    out.append("|---|---|---|---|---|---|---|---|---|---|---|")
    for r in rows:
        if r["status"] != "ok":
            out.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | FAIL {r['status'][:40]} |")
            continue
        rf = r["roofline"]
        mem = r["memory"]["temp_bytes"] / 2**30
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | {mem:.1f} "
            f"| {rf['t_compute_s']:.3g} | {rf['t_memory_s']:.3g} | {rf['t_collective_s']:.3g} "
            f"| {rf['dominant']} | {rf['useful_flops_ratio']:.2f} "
            f"| {rf['roofline_fraction']:.3f} | {_advice(r)} |"
        )
    return out


def _copy_roofline_bytes_per_s(nbytes: int = 1 << 26, trials: int = 3) -> float:
    """Measured streaming-copy bandwidth (read + write) on this device."""
    import jax
    import jax.numpy as jnp

    x = jnp.arange(nbytes // 4, dtype=jnp.int32)
    copy = jax.jit(lambda a: a + 1)
    copy(x).block_until_ready()  # compile outside the timed region
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        copy(x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return 2.0 * nbytes / best


def _serve_bytes(b: int, w: int, v: int) -> int:
    """Bytes the fused serve moves per batch: packed-row read+write,
    probed value-row gather, request-row output, and the fill apply."""
    row = 4 * w * 4  # one packed (4W,) uint32 row
    return b * (2 * row + w * v * 4 + v * 4 + v * 4)


def autotune(
    buckets=(256, 1024, 4096),
    bms=(64, 128, 256, 512),
    trials: int = 3,
    out: str = None,
    quick: bool = False,
) -> dict:
    """Sweep ``bm`` x bucket for the fused serve kernel; persist winners.

    Returns the saved table.  ``quick`` shrinks the sweep to what a CI
    smoke can afford under interpret mode (the table is still written,
    exercised by the broker-pickup test, and uploaded as an artifact).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.cache_ops import serve_fused_op
    from repro.serving import autotune as at

    if quick:
        buckets, bms, trials = (256,), (64, 256), 2
    backend = jax.default_backend()
    s, w, v = 4096, 4, 8
    rng = np.random.default_rng(0)
    ks = jnp.asarray(rng.integers(0, 2**32, size=(s, 4 * w), dtype=np.uint32))
    value = jnp.asarray(rng.integers(0, 2**31, size=(s, w, v), dtype=np.int64).astype(np.int32))
    entries = {}
    for bucket in buckets:
        best = None
        for bm in bms:
            if bm > bucket:
                continue
            args = dict(
                h_hi=jnp.asarray(rng.integers(0, 2**32, size=bucket, dtype=np.uint32)),
                h_lo=jnp.asarray(rng.integers(0, 2**32, size=bucket, dtype=np.uint32)),
                set_idx=jnp.asarray(rng.integers(0, s, size=bucket).astype(np.int32)),
                admit=jnp.ones(bucket, bool),
                static_hit=jnp.zeros(bucket, bool),
                clock=jnp.int32(7),
                f_set_idx=jnp.asarray(rng.integers(0, s, size=bucket).astype(np.int32)),
                f_wrote=jnp.asarray(rng.integers(0, 2, size=bucket).astype(bool)),
                f_way=jnp.asarray(rng.integers(0, w, size=bucket).astype(np.int32)),
                f_values=jnp.zeros((bucket, v), jnp.int32),
            )
            step = jax.jit(
                lambda ks, value, bm=bm, args=args: serve_fused_op(
                    ks, value, use_kernel=True, bm=bm, **args
                )
            )
            jax.tree_util.tree_map(  # compile outside the timed region
                lambda x: x.block_until_ready(), step(ks, value)
            )
            us = float("inf")
            for _ in range(trials):
                t0 = time.perf_counter()
                jax.tree_util.tree_map(
                    lambda x: x.block_until_ready(), step(ks, value)
                )
                us = min(us, (time.perf_counter() - t0) * 1e6)
            entry = dict(bm=bm, us_per_call=round(us, 1))
            if best is None or us < best[0]:
                best = (us, entry)
        roof = _copy_roofline_bytes_per_s()
        bps = _serve_bytes(bucket, w, v) / (best[0] / 1e6)
        best[1]["bytes_per_s"] = round(bps, 1)
        best[1]["frac"] = round(bps / roof, 4)
        entries[f"{backend}/{bucket}"] = best[1]
        print(f"autotune {backend}/{bucket}: bm={best[1]['bm']} "
              f"us/call={best[1]['us_per_call']} frac={best[1]['frac']}")
    table = dict(
        schema=at.AUTOTUNE_SCHEMA,
        roofline_bytes_per_s=round(_copy_roofline_bytes_per_s(), 1),
        entries=entries,
    )
    path = at.save_table(table, out)
    print(f"autotune table -> {path}")
    return table


if __name__ == "__main__":
    argv = sys.argv[1:]
    if "--autotune" in argv:
        argv.remove("--autotune")
        quick = "--quick" in argv
        if quick:
            argv.remove("--quick")
        autotune(out=argv[0] if argv else None, quick=quick)
    else:
        print("\n".join(render(argv[0] if argv else "dryrun_results.json")))
