"""The benchmark's plain reference agrees with the served cache at a
small size, and its control (FIFO sets) does not."""
import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import reference, streams  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.serving.device_cache import splitmix64  # noqa: E402

ENTRIES = 4096
TRAFFIC = {"generator": "drift", "stream_seed": 11, "shuffle_block": 1024,
           "train_phases": 1,
           "params": {"n_requests": 200_000, "n_topics": 16,
                      "queries_per_topic": 3_000, "n_notopic_queries": 5_000,
                      "n_phases": 4}}


def _served(shards, engine, sizes, stream):
    from repro.core.fast import VecLog, VecStats

    args = serve.build_parser().parse_args([
        "--strategy", "STDv_SDC_C2", "--f-ts", "0.5", "--entries", str(ENTRIES),
        "--batch", "256", "--shards", str(shards)])
    spec = dataclasses.replace(serve.spec_from_args(args), engine=engine)
    backend = lambda q: np.repeat(np.asarray(q, np.int32)[:, None], 8, 1)  # noqa: E731
    log = VecLog(keys=stream.keys, n_train=stream.n_train, key_topic=stream.key_topic)
    s = serve.Stream(stream.synth, log, VecStats.from_log(log), stream.key_topic)
    hits, lo = [], 0
    with serve.build_cluster(spec, s, backend) as cluster:
        n_sets = sum(b.cache.n_sets for b in cluster.brokers)
        for n in sizes:
            _, h = cluster.serve(stream.served[lo : lo + n])
            hits.append(h)
            lo += n
    return np.concatenate(hits), n_sets


@pytest.mark.parametrize("shards,engine", [(1, "host"), (4, "host"), (1, "device")])
def test_reference_hit_mask_matches_the_served_cache(shards, engine):
    stream = streams.make(TRAFFIC, 21)
    rng = np.random.default_rng(shards)
    sizes = rng.integers(1, 257, size=80 if engine == "device" else 160)
    got, n_sets = _served(shards, engine, sizes, stream)
    layout = reference.build_layout(stream.keys[: stream.n_train], stream.key_topic,
                                    ENTRIES, 0.5, 0.4, 0.5, 8, shards)
    assert layout.n_sets == n_sets
    keys = stream.served[: sizes.sum()]
    want = reference.replay(layout, keys, stream.key_topic, sizes)
    assert 0.1 < want.mean() < 0.9
    assert np.array_equal(got, want)
    fifo = reference.replay(layout, keys, stream.key_topic, sizes, refresh=False)
    assert (fifo != got).sum() > 0


def test_hash_matches_the_program_and_avoids_the_reserved_words():
    q = np.concatenate([np.arange(10_000), [2**40, 2**62]])
    assert np.array_equal(reference.splitmix64(q), splitmix64(q))
    h = reference.splitmix64(np.arange(100_000))
    assert not np.any(h == 0) and not np.any(h == np.uint64(2**64 - 1))


def test_shares():
    d = {0: 5, 1: 3, 2: 2}
    assert reference.nearest_shares(10, d) == {0: 5, 1: 3, 2: 2}
    assert reference.exact_shares(7, d) == {0: 4, 1: 2, 2: 1}
    assert sum(reference.exact_shares(1001, {0: 1, 1: 1, 2: 1}).values()) == 1001
    assert reference.exact_shares(10, {0: 0, 1: 0}) == {0: 0, 1: 0}


def test_replay_is_batch_atomic_lru():
    # one set of two ways: everything lands in the dynamic section
    layout = reference.Layout(np.zeros(0, np.uint64), 1, 2, [{}], [(0, 1)], 1)
    topic = np.full(10, -1)
    keys = np.array([1, 1, 2, 3, 1, 2, 2])
    # batch 1: [1, 1] both miss (probed before the commit)
    # batch 2: [2, 3] miss, evicting nothing then 1 (LRU: 1 was refreshed
    # by the duplicate, 2 is newer, so 3 evicts 1)
    # batch 3: [1, 2, 2]: 1 misses, 2 hits twice
    hits = reference.replay(layout, keys, topic, np.array([2, 2, 3]))
    assert hits.tolist() == [False, False, False, False, False, True, True]
    fifo = reference.replay(layout, keys, topic, np.array([2, 2, 3]), refresh=False)
    assert fifo.tolist() == hits.tolist()
    keys = np.array([1, 2, 1, 3, 1])
    # LRU: the hit on 1 refreshes it, so 3 evicts 2 and 1 hits again;
    # FIFO: 3 evicts 1
    lru = reference.replay(layout, keys, topic, np.ones(5, int))
    fifo = reference.replay(layout, keys, topic, np.ones(5, int), refresh=False)
    assert lru.tolist() == [False, False, True, False, True]
    assert fifo.tolist() == [False, False, True, False, False]


def test_expected_values_take_the_first_answer_and_flag_missing_queries():
    rk = np.array([5, 3, 5, 9])
    rv = np.array([[50], [30], [51], [90]])
    vals, found = reference.expected_values(np.array([3, 5, 7, 9]), rk, rv)
    assert found.tolist() == [True, True, False, True]
    assert vals[found].ravel().tolist() == [30, 50, 90]
