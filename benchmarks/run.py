"""Benchmark runner: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and mirrors them into a
machine-readable JSON file (``--json-out``, default ``BENCH_serving.json``)
mapping name -> {us_per_call, <derived metrics>} so the perf trajectory is
diffable across PRs.  ``--quick`` shrinks the log and size grid (CI-scale,
~2-3 min); the default reproduces the full scaled paper grid.  ``--lda``
uses the end-to-end LDA pipeline for topic assignment instead of
generator-oracle topics (paper-faithful, slower).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def _git_rev() -> str:
    """The checkout's short commit, or "unknown" where the copy is not a
    git repository or has no git binary."""
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stderr=subprocess.DEVNULL,
        ).decode().strip()
    except (OSError, subprocess.CalledProcessError):
        return "unknown"


def _run_meta(args) -> dict:
    """Provenance of this benchmark run, recorded as the ``meta/run`` row
    so BENCH_serving.json numbers are attributable to an environment."""
    import jax
    import numpy as np

    return {
        "us_per_call": 0.0,
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "numpy_version": np.__version__,
        "python": ".".join(map(str, sys.version_info[:3])),
        "git_rev": _git_rev(),
        "seed": 7,
        "quick": int(args.quick),
        "lda": int(args.lda),
        "scale": 0.2 if args.quick else args.scale,
        "only": args.only or "all",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }


def _row_to_json(row: str):
    """'name,us,k=v;k=v' -> (name, {us_per_call: us, k: v, ...})."""
    name, us, derived = row.split(",", 2)
    out = {"us_per_call": float(us)}
    for kv in derived.split(";"):
        if "=" not in kv:
            continue
        k, v = kv.split("=", 1)
        try:
            out[k] = float(v)
        except ValueError:
            out[k] = v
    return name, out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="small log + 2 sizes")
    ap.add_argument("--lda", action="store_true", help="LDA topics (not oracle)")
    ap.add_argument(
        "--only",
        help="comma-separated subset: table2,table3,table45,table67,"
        "fig6,fig7,drift,load,fault,freshness,perf",
    )
    ap.add_argument(
        "--scale", type=float, default=0.6,
        help="stream-size multiplier over the calibrated 1.5M-request log",
    )
    ap.add_argument(
        "--json-out", default="BENCH_serving.json",
        help="machine-readable mirror of the CSV rows ('' disables)",
    )
    args = ap.parse_args()

    from repro import configure_compile_cache

    configure_compile_cache()

    from . import (
        fig6_miss_distance,
        fig7_fs_sweep,
        fig_drift,
        fig_fault,
        fig_freshness,
        fig_load,
        perf_cache,
        perf_kernels,
        table2_hit_rates,
        table3_belady_gap,
        table45_admission,
        table67_singleton,
    )
    from .common import CACHE_SIZES, QUICK_SIZES

    scale = 0.2 if args.quick else args.scale
    sizes = QUICK_SIZES if args.quick else CACHE_SIZES
    only = set(args.only.split(",")) if args.only else None

    suites = [
        ("table2", lambda: table2_hit_rates.run(sizes, scale=scale, lda=args.lda)),
        ("table3", lambda: table3_belady_gap.run(sizes, scale=scale, lda=args.lda)),
        ("table45", lambda: table45_admission.run(sizes, scale=scale, lda=args.lda)),
        ("table67", lambda: table67_singleton.run(sizes, scale=scale, lda=args.lda)),
        # fig6 needs a cache small relative to the (reduced) log so topic
        # sections actually evict: use the second-smallest size
        ("fig6", lambda: fig6_miss_distance.run(n=sizes[1], scale=min(scale, 0.2))),
        ("fig7", lambda: fig7_fs_sweep.run(sizes[:2], scale=scale)),
        # popularity-drift sweep: frozen vs rebalanced STD (own synthetic
        # stream, independent of the calibrated log)
        ("drift", lambda: fig_drift.run(quick=args.quick)),
        # open-loop load harness: tail latency under arrival processes
        ("load", lambda: fig_load.run(quick=args.quick)),
        # fault episodes: availability/degraded/recovery under injected
        # shard crashes, flaky dispatch, and checkpoint corruption
        ("fault", lambda: fig_fault.run(quick=args.quick)),
        # freshness sweep: hit rate / stale serving / violations vs TTL,
        # plus the invalidation-stream scenario
        ("freshness", lambda: fig_freshness.run(quick=args.quick)),
        ("perf", lambda: perf_cache.run(quick=args.quick) + perf_kernels.run()),
    ]
    print("name,us_per_call,derived")
    results = {}
    for name, fn in suites:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            for row in fn():
                print(row, flush=True)
                row_name, metrics = _row_to_json(row)
                results[row_name] = metrics
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            raise
        print(f"{name}/total_s,{(time.time()-t0)*1e6:.0f},elapsed={time.time()-t0:.1f}s", flush=True)
    if args.json_out and results:
        meta = _run_meta(args)
        # provenance is keyed by git rev so successive runs from different
        # commits keep their own row instead of silently overwriting
        results[f"meta/run/{meta['git_rev']}"] = meta
        # merge into an existing file so a partial (--only/--quick) run
        # refreshes its own rows without dropping the committed table
        merged = {}
        if os.path.exists(args.json_out):
            try:
                with open(args.json_out) as f:
                    merged = json.load(f)
            except (OSError, ValueError):
                merged = {}
        # dedupe provenance: drop the legacy un-keyed row (pre-rev-keyed
        # files); same-rev rows are replaced by the update below
        merged.pop("meta/run", None)
        merged.update(results)
        with open(args.json_out, "w") as f:
            json.dump(merged, f, indent=1, sort_keys=True)
        print(
            f"# wrote {args.json_out} ({len(results)} rows updated, "
            f"{len(merged)} total)",
            file=sys.stderr,
        )


if __name__ == "__main__":
    main()
