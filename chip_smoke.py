#!/usr/bin/env python3
"""Chip smoke: serve the STD result cache on a TPU at deployment size.

Builds the cache exactly as ``python -m repro.launch.serve --strategy
STDv_SDC_C2 --f-ts 0.5`` does -- the calibrated query stream and its LDA
topics generated from a seed, the LM miss backend, ``Cluster.from_spec``
-- at 2^20 entries (W=8 ways, 8 doc ids per result), then serves full
4096-request batches of the stream's test half closed-loop through the
device engine and checks every answer two ways:

* each served value row equals the backend's answer for its query;
* the per-request hit mask equals that of a host-engine (numpy) cluster
  of the same spec serving the same batches -- the engines are bit-exact
  by construction, so any difference is a device fault.

  python chip_smoke.py             # one chip, shards=1
  python chip_smoke.py --chips 4   # hash-routed shards=4, one per chip,
                                   # with a save/restore/recover round trip

Everything runs in this one process (a chip belongs to one process).
The script exits nonzero and prints no result line when JAX finds no
TPU, on any mismatch or misplaced shard, or when any request was served
degraded.  Its last line on success is ``{"ok": true, "device": ...}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

ENTRIES = 1 << 20
REQUESTS = 8_000_000
BATCH = 4096
BATCHES = 200
#: batches served after the four-chip save/restore/recover round trip
AFTER_RECOVERY = 8

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _state_devices(cluster) -> list:
    """Per shard: the positions in ``jax.devices()`` of the devices holding
    any of its state arrays."""
    import jax

    index = {d: k for k, d in enumerate(jax.devices())}
    return [
        sorted({index[d] for leaf in jax.tree.leaves(b.state) for d in leaf.devices()})
        for b in cluster.brokers
    ]


def run(
    entries: int = ENTRIES,
    requests: int = REQUESTS,
    batches: int = BATCHES,
    batch: int = BATCH,
    shards: int = 1,
) -> dict:
    """Serve ``batches`` full batches through a ``shards``-shard cluster on
    the device engine (forced, so a CPU backend runs the same path) and a
    host-engine twin; returns the counts the smoke checks.

    With ``shards > 1`` the device cluster is also saved, restored and
    every shard recovered from the checkpoint, then ``AFTER_RECOVERY``
    more batches are served and compared.  ``placement`` maps each check
    point to the devices (positions in ``jax.devices()``) holding each
    shard's state.
    """
    import jax
    import numpy as np

    from repro.launch import serve

    args = serve.build_parser().parse_args([
        "--strategy", "STDv_SDC_C2", "--f-ts", "0.5",
        "--entries", str(entries), "--requests", str(requests),
        "--batch", str(batch), "--shards", str(shards),
    ])
    spec = dataclasses.replace(serve.spec_from_args(args), engine="device")
    host_spec = dataclasses.replace(spec, engine="host")

    compiles = {"seconds": 0.0, "programs": 0, "cache_hits": 0}

    def on_duration(event, duration, **_):
        if event == _COMPILE_EVENT:
            compiles["seconds"] += duration
            compiles["programs"] += 1

    def on_event(event, **_):
        if event == _CACHE_HIT_EVENT:
            compiles["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        t0 = time.perf_counter()
        stream = serve.build_stream(requests)
        backend = serve.build_backend(args.arch, args.value_dim, chunk=batch)
        test = stream.log.test_keys
        n_after = AFTER_RECOVERY if shards > 1 else 0
        if len(test) < (batches + n_after) * batch:
            raise ValueError(
                f"the test half holds {len(test)} requests, fewer than "
                f"{batches + n_after} batches of {batch}"
            )
        print(f"stream: {requests} requests, {len(test)} in the test half, "
            f"built in {time.perf_counter() - t0:.3f}s")
        out = dict(value_mismatches=0, hit_mismatches=0, batches=0, requests=0)
        t0 = time.perf_counter()
        with serve.build_cluster(spec, stream, backend) as dev, \
                serve.build_cluster(host_spec, stream, backend) as host:
            print(f"clusters built in {time.perf_counter() - t0:.3f}s "
                "(static-layer preload through the backend included)")

            def serve_batches(k0: int, n: int) -> None:
                for k in range(k0, k0 + n):
                    q = test[k * batch : (k + 1) * batch]
                    t = time.perf_counter()
                    values, hit = dev.serve(q)
                    if k == 0:
                        out["first_batch_s"] = time.perf_counter() - t
                    _, host_hit = host.serve(q)
                    out["value_mismatches"] += int(
                        (~np.all(values == backend(q), axis=1)).sum()
                    )
                    out["hit_mismatches"] += int((hit != host_hit).sum())
                    out["batches"] += 1
                    out["requests"] += len(q)

            t0 = time.perf_counter()
            serve_batches(0, batches)
            out["serve_s"] = time.perf_counter() - t0
            placement = {"served": _state_devices(dev)}
            if shards > 1:
                with tempfile.TemporaryDirectory(prefix="chip_smoke_") as ckpt:
                    dev.save(ckpt, step=1)
                    dev.restore(ckpt)
                    placement["restored"] = _state_devices(dev)
                    for i in range(shards):
                        dev.recover_shard(i)
                    placement["recovered"] = _state_devices(dev)
                serve_batches(batches, n_after)
                placement["served_after_recovery"] = _state_devices(dev)
            s = dev.stats
            out.update(
                placement=placement,
                state_bytes=sum(
                    leaf.nbytes
                    for b in dev.brokers
                    for leaf in jax.tree.leaves(b.state)
                ),
                hit_rate=s.hit_rate,
                static_hits=s.static_hits,
                topic_hits=s.topic_hits,
                host_hit_rate=host.stats.hit_rate,
                degraded=s.degraded,
                failed_over=s.failed_over,
                dispatch_counts=dict(dev.dispatch_counts),
                trace_counts=dict(dev.trace_counts),
                n_sets=[b.cache.n_sets for b in dev.brokers],
                static_entries=[
                    int(b.state["static_hi"].shape[0]) for b in dev.brokers
                ],
            )
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)
    out.update(
        compile_s=compiles["seconds"],
        compiled_programs=compiles["programs"],
        compile_cache_hits=compiles["cache_hits"],
    )
    return out


def placement_errors(placement: dict, n_devices: int) -> list:
    """Check points where shard i's state is not on exactly device i (with
    one shard: device 0)."""
    errors = []
    for point, per_shard in placement.items():
        for i, ids in enumerate(per_shard):
            want = [i % n_devices]
            if ids != want:
                errors.append(f"{point}: shard {i} state on devices {ids}, want {want}")
    return errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1: one cluster shard on one chip; 4: a hash-routed "
        "four-shard cluster, one shard per chip",
    )
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    from repro import configure_compile_cache

    cache_dir = configure_compile_cache()
    kind = devices[0].device_kind
    print(f"device_kind={kind} devices={len(devices)} compile_cache={cache_dir}")
    print(f"entries={ENTRIES} shards={args.chips} batch={BATCH} "
          f"requests_generated={REQUESTS}")
    r = run(shards=args.chips)
    print(f"state_bytes_on_device={r['state_bytes']} sets_per_shard={r['n_sets']} "
          f"static_entries_per_shard={r['static_entries']}")
    print(f"compile_s={r['compile_s']:.3f} compiled_programs={r['compiled_programs']} "
          f"compile_cache_hits={r['compile_cache_hits']} "
          f"first_batch_s={r['first_batch_s']:.3f}")
    print(f"batches={r['batches']} requests={r['requests']} serve_s={r['serve_s']:.3f} "
          f"hit_rate={r['hit_rate']:.6f} host_engine_hit_rate={r['host_hit_rate']:.6f} "
          f"static_hits={r['static_hits']} topic_hits={r['topic_hits']}")
    print(f"dispatch_counts={r['dispatch_counts']} trace_counts={r['trace_counts']}")
    print(f"value_mismatches={r['value_mismatches']} "
          f"hit_mismatches={r['hit_mismatches']} "
          f"degraded={r['degraded']} failed_over={r['failed_over']}")
    for point, ids in r["placement"].items():
        print(f"placement {point}: shard -> jax.devices() index {ids}")
    errors = placement_errors(r["placement"], len(devices))
    for e in errors:
        print(f"chip_smoke: misplaced state: {e}", file=sys.stderr)
    failed = (
        errors
        or r["value_mismatches"]
        or r["hit_mismatches"]
        or r["degraded"]
        or r["failed_over"]
        or r["batches"] < BATCHES
    )
    if failed:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": platform, "kind": kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
