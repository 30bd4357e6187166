"""Infrastructure perf: device-cache probe/commit + reuse-distance engine.

Timings are for whatever backend runs the benchmark (the ``meta/run`` row
names it).  Run on a CPU host they measure the framework's host-side
constants and the vectorized-engine speedup over the sequential
reference, not device throughput.

Commit timings chain states (``state = commit(state, ...)``) so each call
depends on the previous one's result -- measuring dependent update
throughput, which is what a serving broker experiences, rather than N
independent replays of the same initial state.

The commit rows compare three engines over identical batches:

* ``cache_commit_seq``     -- the fori_loop oracle (reference semantics)
* ``cache_commit_vec``     -- the conflict-aware batch commit on the host
  engine, which is what the broker serves with on CPU backends
* ``cache_commit_vec_xla`` -- the same algorithm as jnp ops; on a CPU
  host XLA prices a B-index scatter at ~170ns/index, so this row
  mostly documents why the host engine exists (on accelerators the
  jnp/Pallas engines take over and the scatter objection disappears)

The commit batches use an empty static set: the static layer is read-only
and its lookup cost is identical in every engine (the probe rows measure
it), so the commit rows isolate the update machinery being compared.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CacheSpec, VecLog, VecStats
from repro.core.fast import partitioned_prev
from repro.core.rd_offline import reuse_distances_offline
from repro.core.jax_sim import reuse_distances_py
from repro.serving import (
    Broker,
    BucketSpec,
    Cluster,
    DeviceCacheConfig,
    DispatchSpec,
    STDDeviceCache,
    ServingSpec,
    pack_hashes,
    splitmix64,
)

from .common import best_of_us, csv_row


def _block(tree):
    leaf = jax.tree.leaves(tree)[0]
    if hasattr(leaf, "block_until_ready"):
        leaf.block_until_ready()


def _chain_us(commit, make_state, args, reps: int) -> float:
    """us/call for state-chained commits (dependent, not independent).

    Every engine runs under the serving contract ``state = commit(state,
    ...)``: the previous state is consumed, so the jit engines get buffer
    donation and the host engine mutates in place.  ``make_state`` hands
    each chain a fresh private state.
    """
    s = commit(make_state(), *args)  # compile + warm
    _block(s)
    s = make_state()
    gc.collect()  # park the collector: chains allocate per-call garbage
    t0 = time.time()
    for _ in range(reps):
        s = commit(s, *args)
    _block(s)
    return (time.time() - t0) / reps * 1e6


def run(quick: bool = False) -> List[str]:
    rows: List[str] = []
    rng = np.random.default_rng(0)

    # device cache probe/commit throughput (probe keeps its static set;
    # commit batches use an empty one, see module docstring)
    cfg = DeviceCacheConfig.build(
        65536, f_s=0.2, f_t=0.6, topic_distinct={t: 100 for t in range(64)}, ways=8
    )
    cache = STDDeviceCache(cfg, static_hashes=splitmix64(np.arange(1, 2000)))
    state = dict(cache.init_state)
    bare = STDDeviceCache(cfg)
    dev_state = lambda: {k: jnp.array(v) for k, v in bare.init_state.items()}
    host_state = lambda: {k: np.array(v) for k, v in bare.init_state.items()}
    probe = jax.jit(cache.probe)
    commit_seq = jax.jit(bare.commit, donate_argnums=0)
    commit_vec_xla = jax.jit(bare.commit_vectorized, donate_argnums=0)
    commit_vec = lambda s, *a: bare.commit_host(s, *a, inplace=True)
    xla_nsq = {}
    vec_nsq = {}
    for batch in (256, 4096):
        qids = rng.integers(0, 200_000, size=batch)
        topics = rng.integers(-1, 64, size=batch)
        parts = jnp.asarray(cache.parts_for(topics))
        h_hi, h_lo = pack_hashes(splitmix64(qids))
        h_hi, h_lo = jnp.asarray(h_hi), jnp.asarray(h_lo)
        vals = jnp.zeros((batch, cfg.value_dim), jnp.int32)
        admit = jnp.ones(batch, bool)
        probe(state, h_hi, h_lo, parts)[0].block_until_ready()  # compile
        t0 = time.time()
        reps = 20
        for _ in range(reps):
            hit = probe(state, h_hi, h_lo, parts)[0]
        hit.block_until_ready()
        us = (time.time() - t0) / reps * 1e6
        rows.append(
            csv_row(f"perf/cache_probe/B={batch}", us, f"ns_per_query={us*1000/batch:.0f}")
        )
        args = (h_hi, h_lo, parts, vals, admit)
        seq_reps = 3 if (quick or batch >= 4096) else 5
        seq_us = _chain_us(commit_seq, dev_state, args, seq_reps)
        rows.append(
            csv_row(
                f"perf/cache_commit_seq/B={batch}",
                seq_us,
                f"ns_per_query={seq_us*1000/batch:.0f}",
            )
        )
        host_args = (np.asarray(h_hi), np.asarray(h_lo), np.asarray(parts),
                     np.asarray(vals), np.asarray(admit))
        vec_us = min(
            _chain_us(commit_vec, host_state, host_args, 10 if quick else 30)
            for _ in range(3)
        )
        vec_nsq[batch] = vec_us * 1000 / batch
        rows.append(
            csv_row(
                f"perf/cache_commit_vec/B={batch}",
                vec_us,
                f"ns_per_query={vec_us*1000/batch:.0f};speedup_vs_seq={seq_us/vec_us:.1f}",
            )
        )
        # min-of-3 chains: single-chain timing jitters +-30% on shared
        # hosts, far above the batch-scaling margin asserted below
        xla_us = min(
            _chain_us(commit_vec_xla, dev_state, args, 5 if quick else 10)
            for _ in range(3)
        )
        xla_nsq[batch] = xla_us * 1000 / batch
        rows.append(
            csv_row(
                f"perf/cache_commit_vec_xla/B={batch}",
                xla_us,
                f"ns_per_query={xla_us*1000/batch:.0f};speedup_vs_seq={seq_us/xla_us:.1f}",
            )
        )

    # batch-scaling regression for the vec_xla engine.  The investigated
    # anomaly was real but misattributed: not a missing donation or a
    # re-pack copy, but XLA-CPU scatter pricing (~170 ns/index) -- the
    # probe-output scatters and the per-round write-plan scatters cost
    # O(B) *per round*, and six un-sort scatters another O(B) per call.
    # Hoisting the probe outputs, rank-masking the rounds loop
    # (gather+where), and un-sorting through one inverse permutation cut
    # B=4096 from ~1540 to ~1050 ns/q (B=256 improved identically).
    # What remains is linear-in-B work whose depth term *grows* with B
    # (3 conflict rounds at B=256 vs 6 at B=4096 here), so per-query
    # cost is flat by construction, not amortizing: the assert pins
    # non-degradation -- a reintroduced per-round scatter shows up as
    # B=4096 ns/q well above B=256 (the old pathology at larger B).
    assert xla_nsq[4096] <= 1.15 * xla_nsq[256], (
        f"vec_xla per-query cost degrades with batch size: "
        f"{xla_nsq[4096]:.0f} ns/q at B=4096 vs {xla_nsq[256]:.0f} at B=256"
    )
    # ...and the ratio alone cannot distinguish the old pathology (flat
    # at ~1540 ns/q) from the fixed engine (flat at ~1050), so also pin
    # the same-run gap against the numpy host engine: pre-fix it was
    # 3.3-3.4x, post-fix ~2.3x.  Same machine, same batch, same states
    # -- the ratio is load-robust where an absolute ns/q pin is not.
    assert xla_nsq[4096] <= 3.0 * vec_nsq[4096], (
        f"vec_xla lost ground to the host engine (scatter regression?): "
        f"{xla_nsq[4096]:.0f} ns/q vs host {vec_nsq[4096]:.0f} at B=4096"
    )

    # adversarial forced-conflict batch: every request hashes to one set,
    # so the conflict depth -- the only sequential dimension left --
    # degrades to B, the oracle's regime.  This is the floor of the
    # speedup, not the typical case: hashed traffic keeps depth near
    # ceil(B / live sets).
    batch = 256 if quick else 1024
    n_dyn_sets = max(int(cache.part_sets[cache.k]), 1)
    cand = np.arange(1, 4_000_000)
    cand_set = (splitmix64(cand) & np.uint64(0xFFFFFFFF)).astype(np.int64) % n_dyn_sets
    qids = cand[cand_set == cand_set[0]][:batch]
    assert len(qids) == batch, "raise the candidate range"
    parts = jnp.asarray(np.full(batch, cache.k, np.int32))
    h_hi, h_lo = pack_hashes(splitmix64(qids))
    args = (
        jnp.asarray(h_hi),
        jnp.asarray(h_lo),
        parts,
        jnp.zeros((batch, cfg.value_dim), jnp.int32),
        jnp.ones(batch, bool),
    )
    seq_us = _chain_us(commit_seq, dev_state, args, 2)
    host_args = (np.asarray(args[0]), np.asarray(args[1]), np.asarray(parts),
                 np.asarray(args[3]), np.asarray(args[4]))
    vec_us = _chain_us(commit_vec, host_state, host_args, 2)
    rows.append(
        csv_row(
            f"perf/cache_commit_vec_adversarial/B={batch}",
            vec_us,
            f"ns_per_query={vec_us*1000/batch:.0f};speedup_vs_seq={seq_us/vec_us:.2f}",
        )
    )

    # end-to-end fused serving: broker round-trips per batch, trivial
    # backend so the cache path dominates.  serve_fused is the legacy
    # fused/fused_fill pair (fused_one_call=False); serve_one_call is the
    # PR-10 default one-dispatch path over the *same* stream, so CI can
    # assert one-call <= legacy on ns_per_query within one run.  Both use
    # best-of-3 gc-parked trials over the rep loop.
    def backend(qids):
        return np.tile(qids[:, None], (1, cfg.value_dim)).astype(np.int32)

    topic_arr = rng.integers(-1, 64, size=200_000)
    for batch in (256, 4096):
        stream = rng.integers(0, 20_000, size=(6, batch))  # reuse -> hits
        # enough reps x trials that the one-call-vs-legacy CI compare
        # (1.2x margin) sits above the run-to-run jitter, which at
        # reps=2 spanned 0.8-1.3x on this container
        reps = 6 if quick else 10
        for name, one_call in (("serve_fused", False), ("serve_one_call", True)):
            broker = Broker(
                STDDeviceCache(cfg, static_hashes=splitmix64(np.arange(1, 2000))),
                [backend],
                topic_of=lambda q: topic_arr[q],
                engine="device",  # auto picks host on CPU; pin the jit path
                fused_one_call=one_call,
            )
            broker.serve(stream[0])  # compile + warm the cache

            def loop():
                for i in range(reps):
                    broker.serve(stream[1 + i % 5])

            us = best_of_us(loop, trials=5) / reps
            if one_call:
                assert broker.dispatch_counts.get("one_call", 0) > 0
            rows.append(
                csv_row(
                    f"perf/{name}/B={batch}",
                    us,
                    f"ns_per_query={us*1000/batch:.0f};"
                    f"hit_rate={broker.stats.hit_rate:.3f}",
                )
            )
            broker.close()

    # shape-bucketed serving of a ragged stream on the jit-compiled
    # device engine: batch lengths vary per batch, so the unpadded path
    # re-traces the fused step once per distinct shape while the bucketed
    # path (reserved pad key) compiles O(#buckets).  Wall time includes
    # the compiles -- recompile jitter is exactly what bucketing removes.
    # The CI smoke asserts the compile-count bound.
    ragged_rng = np.random.default_rng(7)
    n_batches = 12 if quick else 24
    ragged = [int(s) for s in ragged_rng.integers(1, 257, size=n_batches)]
    # pre-generate the stream so both runs serve *identical* requests --
    # the row compares padding vs no padding, not workload variation
    ragged_stream = [ragged_rng.integers(0, 20_000, size=bsz) for bsz in ragged]
    bucket = BucketSpec(min_size=8)

    def _ragged_serve(bspec, defer):
        broker = Broker(
            STDDeviceCache(cfg, static_hashes=splitmix64(np.arange(1, 2000))),
            [backend],
            topic_of=lambda q: topic_arr[q],
            engine="device",
            bucket=bspec,
            defer_fill=defer,
        )
        t0 = time.time()
        for q in ragged_stream:
            broker.serve(q)
        broker.flush()
        dt = time.time() - t0
        fused = (
            broker.trace_counts.get("fused", 0)
            + broker.trace_counts.get("fused_fill", 0)
            + broker.trace_counts.get("one_call", 0)
        )
        broker.close()
        return dt, fused, broker.stats

    plain_s, plain_traces, _ = _ragged_serve(BucketSpec(mode="none"), False)
    buck_s, buck_traces, bstats = _ragged_serve(bucket, True)
    n_buckets = len({bucket.padded_len(b) for b in ragged})
    assert buck_traces <= 2 * n_buckets, (
        f"compile-count bound violated: {buck_traces} fused traces for "
        f"{n_buckets} buckets"
    )
    pad_frac = bstats.padded / max(bstats.requests + bstats.padded, 1)
    rows.append(
        csv_row(
            f"perf/serve_bucketed/batches={n_batches}",
            buck_s / n_batches * 1e6,
            f"unpadded_us={plain_s / n_batches * 1e6:.0f};"
            f"speedup_vs_unpadded={plain_s / buck_s:.2f};"
            f"compiles_bucketed={buck_traces};"
            f"compiles_unpadded={plain_traces};"
            f"buckets={n_buckets};pad_frac={pad_frac:.3f}",
        )
    )

    # fused serving through a spec-compiled cluster: shards=1 (the bare
    # broker path, request-for-request identical by the conformance tests)
    # vs shards=4 hash routing at the same total entries -- measures the
    # scatter-gather overhead and the cross-shard overlap on one host
    nq = 50_000
    key_topic = rng.integers(-1, 64, size=nq).astype(np.int64)
    keys = rng.integers(0, 20_000, size=40_000).astype(np.int64)  # reuse -> hits
    vstats = VecStats.from_log(VecLog(keys=keys, n_train=20_000, key_topic=key_topic))
    sspec = ServingSpec(
        cache=CacheSpec.from_strategy("STDv_LRU", 65536, f_s=0.2, f_t=0.6),
        value_dim=cfg.value_dim,
    )
    batch = 1024 if quick else 4096
    stream = rng.integers(0, 20_000, size=(6, batch))
    reps = 16 if quick else 32
    for shards in (1, 4):
        # shards=1 serves synchronously: its conformance contract (request-
        # for-request identical to a bare Broker, hit masks included)
        # forbids cross-batch fusion.  shards=4 runs the pipelined async
        # dispatcher, which fuses queued per-shard segments across batches
        # and amortizes the fixed per-broker-call cost.  Best of 3 trials
        # (fresh cluster each, gc parked) -- the CI smoke asserts the
        # shards=4 row beats shards=1 on ns_per_query, so the row must
        # report the machine, not a scheduler hiccup.
        best_us, hit_rate = float("inf"), 0.0
        for _ in range(3):
            with Cluster.from_spec(
                dataclasses.replace(
                    sspec,
                    shards=shards,
                    dispatch=DispatchSpec() if shards > 1 else None,
                ),
                vstats, [backend], value_fn=backend,
            ) as cluster:
                cluster.serve(stream[0])  # compile + warm the caches
                gc.collect()
                t0 = time.time()
                if shards == 1:
                    for i in range(reps):
                        cluster.serve(stream[1 + i % 5])
                else:
                    futs = [
                        cluster.serve_async(stream[1 + i % 5])
                        for i in range(reps)
                    ]
                    for f in futs:
                        f.result()
                best_us = min(best_us, (time.time() - t0) / reps * 1e6)
                hit_rate = cluster.stats.hit_rate
        rows.append(
            csv_row(
                f"perf/serve_cluster/shards={shards}/B={batch}",
                best_us,
                f"ns_per_query={best_us*1000/batch:.0f};"
                f"hit_rate={hit_rate:.3f}",
            )
        )

    # reuse-distance engine vs sequential Fenwick
    n = 100_000 if quick else 500_000
    keys = rng.integers(0, n // 5, size=n).astype(np.int64)
    part = np.zeros(n, dtype=np.int64)
    order, prev = partitioned_prev(keys, part)
    t0 = time.time()
    rd_fast = reuse_distances_offline(prev)
    fast_s = time.time() - t0
    t0 = time.time()
    rd_ref = reuse_distances_py(prev[:50_000])
    ref_s = (time.time() - t0) * (n / 50_000)
    assert (rd_fast[:50_000] == rd_ref).all()
    rows.append(
        csv_row(
            f"perf/reuse_distance/n={n//1000}k",
            fast_s * 1e6,
            f"Mreq_per_s={n/fast_s/1e6:.2f};speedup_vs_fenwick={ref_s/fast_s:.1f}",
        )
    )
    return rows
