"""What a cluster of more than one shard adds to the serving path: the
front end's count of each batch's largest shard slice, on the shard that
holds it, which follows the routing whether or not queued batches fuse
and survives checkpoints (older ones restore it as 0).
"""
from __future__ import annotations

import dataclasses
import os
import tempfile

import numpy as np
import pytest

from repro.core import CacheSpec, VecLog, VecStats
from repro.serving import BucketSpec, Cluster, DispatchSpec, ServingSpec

BATCHES = ((0, 64), (64, 37), (101, 3), (104, 64))


def _cluster(shards, engine="host", dispatch=None):
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 2000, size=10000)
    topic = rng.integers(-1, 4, size=2000)
    stats = VecStats.from_log(VecLog(keys=keys, n_train=4000, key_topic=topic))
    backend = lambda q: np.tile(np.asarray(q)[:, None], (1, 2)).astype(np.int32)  # noqa: E731
    spec = ServingSpec(
        cache=CacheSpec.from_strategy("STDv_LRU", 512, f_s=0.2, f_t=0.6),
        value_dim=2, shards=shards, engine=engine, microbatch=64,
        bucket=BucketSpec(min_size=8) if engine == "device" else BucketSpec(mode="none"),
        dispatch=dispatch,
    )
    return Cluster.from_spec(spec, stats, [backend], value_fn=backend), keys[4000:]


def _largest_slices(cluster, stream):
    """Per shard: its slice's requests in the batches where it was the
    largest (the first such shard on a tie)."""
    want = [0] * 4
    for lo, n in BATCHES:
        slices = np.bincount(cluster.spec.shard_of(stream[lo : lo + n]), minlength=4)
        want[int(slices.argmax())] += int(slices.max())
    return want


@pytest.mark.parametrize("engine", ["host", "device"])
def test_slice_counters_follow_the_routing(engine):
    """Serial (host engine) and threaded (device engine) scatter alike:
    each batch's largest slice, on the shard that holds it."""
    cluster, stream = _cluster(4, engine)
    with cluster:
        for lo, n in BATCHES:
            cluster.serve(stream[lo : lo + n])
        want = _largest_slices(cluster, stream)
        assert [s.slice_max_requests for s in cluster.shard_stats] == want
        s = cluster.stats
        assert s.slice_max_requests == sum(want) > s.requests / 4


def test_fused_drain_steps_count_each_batchs_largest_slice():
    """Queued batches fuse into one call per shard; the count stays per
    batch, as if each had been served alone."""
    cluster, stream = _cluster(4, dispatch=DispatchSpec(max_fuse=8, fuse_requests=4096))
    with cluster:
        futs = [cluster.serve_async(stream[lo : lo + n]) for lo, n in BATCHES]
        futs[-1].result()
        assert all(f.done() for f in futs)
        assert sum(b.stats.batches for b in cluster.brokers) == 4  # one fused call per shard
        want = _largest_slices(cluster, stream)
        assert [s.slice_max_requests for s in cluster.shard_stats] == want


def test_one_shard_counts_no_slices():
    cluster, stream = _cluster(1)
    with cluster:
        for lo in range(0, 256, 64):
            cluster.serve(stream[lo : lo + 64])
        assert cluster.stats.requests == 256
        assert cluster.stats.slice_max_requests == 0


def test_cluster_checkpoint_without_the_slice_counters_restores_them_as_zero():
    cluster, stream = _cluster(4)
    with cluster, tempfile.TemporaryDirectory() as d:
        for lo in range(0, 256, 64):
            cluster.serve(stream[lo : lo + 64])
        assert cluster.stats.slice_max_requests
        cluster.save(d, 1)
        for i in range(4):
            npz = os.path.join(d, f"shard_{i:03d}", "step_0000000001", "arrays.npz")
            arrays = dict(np.load(npz))
            del arrays["stats/slice_max_requests"]
            np.savez(npz, **arrays)
        fresh, _ = _cluster(4)
        with fresh:
            fresh.serve(stream[:64])  # nonzero counters before the restore
            assert fresh.restore(d) == 1
            got = dataclasses.asdict(fresh.stats)
            want = dataclasses.asdict(cluster.stats)
            assert got.pop("slice_max_requests") == 0
            want.pop("slice_max_requests")
            assert got == want
