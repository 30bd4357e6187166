"""The benchmark's copies of the stream generators and arrival processes
make what the program's originals make today, seed for seed."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import arrivals, streams, synth  # noqa: E402
from repro.loadgen import ArrivalSpec  # noqa: E402
from repro.querylog import synth as prog  # noqa: E402

SEEDS = [0, 11, 2**31 + 5]


def _same_log(a, b):
    assert np.array_equal(a.keys, b.keys)
    assert np.array_equal(a.timestamps, b.timestamps)
    assert np.array_equal(a.true_topic, b.true_topic)
    assert np.array_equal(a.n_terms, b.n_terms)
    assert np.array_equal(a.n_chars, b.n_chars)
    assert sorted(a.docs) == sorted(b.docs)
    for q in a.docs:
        assert np.array_equal(a.docs[q], b.docs[q])
    if a.clicks is None:
        assert b.clicks is None
    else:
        assert np.array_equal(a.clicks, b.clicks)


@pytest.mark.parametrize("seed", SEEDS)
def test_calibrated_generator_matches_program(seed):
    kw = dict(n_requests=20_000, n_topics=16, n_topical_queries=2_000,
              n_notopic_queries=1_000, vocab_size=512)
    _same_log(synth.generate(synth.SynthConfig(seed=seed, **kw)),
              prog.generate(prog.SynthConfig(seed=seed, **kw)))


@pytest.mark.parametrize("seed", SEEDS)
def test_drift_generator_matches_program(seed):
    kw = dict(n_requests=40_000, n_topics=16, queries_per_topic=625,
              n_notopic_queries=1_000, n_phases=4)
    _same_log(synth.generate_drifting(synth.DriftConfig(seed=seed, **kw)),
              prog.generate_drifting(prog.DriftConfig(seed=seed, **kw)))


@pytest.mark.parametrize("seed", SEEDS)
def test_poisson_arrivals_match_program(seed):
    got = arrivals.poisson_times(seed, 50_000.0, 5_000)
    want = ArrivalSpec(process="poisson", rate=50_000.0, seed=seed).times(5_000)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("seed", SEEDS)
def test_onoff_arrivals_match_program(seed):
    got = arrivals.onoff_times(seed, 50_000.0, 5_000, burst=4.0, on_frac=0.2,
                               mean_on_s=0.02)
    want = ArrivalSpec(process="onoff", rate=50_000.0, burst=4.0, on_frac=0.2,
                       mean_on_s=0.02, seed=seed).times(5_000)
    assert np.array_equal(got, want)


DRIFT = {"generator": "drift", "stream_seed": 11, "shuffle_block": 1000,
         "train_phases": 1,
         "params": {"n_requests": 40_500, "n_topics": 16, "queries_per_topic": 625,
                    "n_notopic_queries": 1_000, "n_phases": 4}}


def test_drift_stream_trains_on_its_first_phase():
    s = streams.make(DRIFT, 3)
    assert s.n_train == 10_125
    assert len(s.served) == 30_375
    assert np.array_equal(s.key_topic, s.synth.true_topic)
    want = synth.generate_drifting(synth.DriftConfig(seed=11, **DRIFT["params"]))
    assert np.array_equal(s.keys[: s.n_train], want.keys[: s.n_train])


def test_the_seed_reorders_the_served_part_within_blocks():
    a, b = streams.make(DRIFT, 3), streams.make(DRIFT, 2**31 + 3)
    assert np.array_equal(a.keys, streams.make(DRIFT, 3).keys)
    assert np.array_equal(a.keys[: a.n_train], b.keys[: b.n_train])
    assert not np.array_equal(a.served, b.served)
    base = streams.generate(DRIFT, 11).served
    for lo in range(0, len(base), 1000):  # the tail block is shorter
        blk = slice(lo, lo + 1000)
        assert np.array_equal(np.sort(a.served[blk]), np.sort(base[blk]))
        assert np.array_equal(np.sort(b.served[blk]), np.sort(base[blk]))


def test_calibrated_stream_takes_topics_from_the_pipeline():
    traffic = {"generator": "calibrated", "stream_seed": 11, "shuffle_block": 512,
               "params": {"n_requests": 20_000, "n_topics": 16,
                          "n_topical_queries": 2_000, "n_notopic_queries": 1_000,
                          "vocab_size": 512},
               "topics": {"train_frac": 0.5}}
    seen = {}

    def pipeline(log, **params):
        seen.update(params)
        return np.full(log.n_queries, 7), len(log.keys) // 2

    s = streams.make(traffic, 5, topic_pipeline=pipeline)
    assert seen == {"train_frac": 0.5}
    assert s.n_train == 10_000 and (s.key_topic == 7).all()
    with pytest.raises(ValueError):
        streams.make(traffic, 5)
