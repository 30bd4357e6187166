"""Device idle named by the program's own spans (``chipbench/phases.py``):
on synthetic spans, innermost first and the program's before the
benchmark's, the pieces summing to the idle time; on a small run on
the CPU, the spans and counters of every window batch."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness, phases, trace  # noqa: E402
from chipbench.phases import Span  # noqa: E402
from chipbench.trace import Event  # noqa: E402


def _span(name, a, b, **meta):
    return Span(name, a, b, meta)


def _synthetic():
    # window [0, 100); device busy [20, 30) and [60, 70)
    ops = [Event("%fusion.1", 20, 30), Event("%fusion.2", 60, 70)]
    spans = [
        _span("bench.window", 0, 100),
        _span("bench.serve", 0, 90),
        _span("cluster.serve", 2, 88, seq=0),
        _span("cluster.route", 2, 8, seq=0),
        _span("broker.serve", 10, 86, seq=0, shard=0),
        _span("broker.prepare", 10, 15, seq=0),
        _span("broker.dispatch", 15, 20, seq=0),
        _span("broker.readback", 20, 40, seq=0),
        _span("broker.miss", 40, 60, seq=0),
        _span("bench.backend", 45, 55),
        _span("broker.fill_plan", 70, 82, seq=0),
    ]
    busy = phases.busy_intervals({0: {trace.OPS_LINE: ops}}, 0, 100)
    return busy, spans


def test_idle_goes_to_the_innermost_program_span():
    busy, spans = _synthetic()
    got = phases.idle_by_span(busy, spans, 0, 100)
    want = {
        "bench.serve": 2e-9 + 2e-9,    # [0, 2) and [88, 90): outside every program span
        "cluster.serve": 2e-9 + 2e-9,  # [8, 10) and [86, 88)
        "cluster.route": 6e-9,
        "broker.prepare": 5e-9,
        "broker.dispatch": 5e-9,
        "broker.readback": 10e-9,      # [30, 40): the step ran in [20, 30)
        "broker.miss": 20e-9,          # the backend inside it counts to it
        "broker.serve": 4e-9,          # [82, 86)
        "broker.fill_plan": 12e-9,
        "idle": 10e-9,                 # [90, 100): no span at all
    }
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k]), k
    assert sum(got.values()) == pytest.approx(80e-9)  # 100 - 20 busy


def test_pieces_never_exceed_the_idle_time_over_devices():
    busy, spans = _synthetic()
    busy[1] = np.array([[0, 50]], np.int64)
    got = phases.idle_by_span(busy, spans, 0, 100)
    assert sum(got.values()) == pytest.approx((80e-9 + 50e-9) / 2)
    assert all(v > 0 for v in got.values())


def test_overlapping_threads_go_to_the_latest_started_span():
    # two shards' slices on their own threads; where both cover the gap,
    # the later-started one takes it
    busy = {0: np.array([[0, 10]], np.int64)}
    spans = [_span("bench.window", 0, 100), _span("broker.readback", 10, 60),
             _span("broker.miss", 30, 100)]
    got = phases.idle_by_span(busy, spans, 0, 100)
    assert got == {"broker.readback": pytest.approx(20e-9),
                   "broker.miss": pytest.approx(70e-9)}


def test_longest_gaps_name_their_span_and_batch():
    busy, spans = _synthetic()
    gaps = phases.longest_gaps(busy, spans, 0, 100, top=2)
    # [70, 100) and [30, 60), 30 ns each; the batch is the innermost
    # serve span around the gap's middle
    assert gaps == [
        {"device": 0, "ms": pytest.approx(30e-6), "span": "broker.fill_plan", "seq": 0},
        {"device": 0, "ms": pytest.approx(30e-6), "span": "broker.miss", "seq": 0},
    ]


def _tiny_cell():
    with open(os.path.join(ROOT, "chipbench", "configs", "std_c2_1chip.json")) as f:
        cfg = json.load(f)
    cfg.update(entries=4096, shards=1)
    traffic = {"generator": "drift", "stream_seed": 11, "shuffle_block": 1024,
               "train_phases": 1, "loop": "closed", "max_batch": 256,
               "params": {"n_requests": 400_000, "n_topics": 16,
                          "queries_per_topic": 6_000, "n_notopic_queries": 10_000,
                          "n_phases": 4}}
    return harness.Cell("tiny.closed", cfg, traffic, 1, [], [])


def test_a_traced_window_on_the_cpu(tmp_path):
    from jax._src.profiler import ProfileData

    seed = 2**31 + 5
    setup = harness.set_up(_tiny_cell(), seed)
    cluster = setup.cluster
    s0, t0 = cluster.stats, dict(cluster.trace_counts)
    res, _ = harness.measure(setup, seed, 0.5, True, str(tmp_path))
    n = len(res.batch_sizes)
    got = phases.counters(s0, cluster.stats, t0, cluster.trace_counts, n)
    cluster.close()
    assert n > 0
    # the one-call path: hit, layer, stale, value, wrote, set_idx, way;
    # four fill-plan arrays and six request arrays
    assert got["readbacks"] == 7 and got["uploads"] == 10
    assert got["replay_rounds"] >= 1
    assert got["window_traces"] == 0
    out = phases.reduce(ProfileData.from_file(trace.find_xplane(str(tmp_path))), n)
    # cluster.serve, cluster.route, broker.serve and its five phases
    assert out["spans_per_batch"] == 8
    assert set(out["idle_ms_by_phase"]) == {"prepare", "readback", "miss"}
