"""The trace reduction: busy union, per-program device time, idle gaps
named by host span, top operations; on synthetic events and on a small
trace recorded on a v5e chip."""
import gzip
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import trace  # noqa: E402
from chipbench.trace import Event  # noqa: E402

MODULES = {"cache_step": ["^jit_wrapper\\b"]}


def test_union_and_clip():
    iv = np.array([[5, 9], [0, 2], [1, 3], [8, 12], [20, 21]])
    assert trace.union(iv).tolist() == [[0, 3], [5, 12], [20, 21]]
    assert trace.clip(trace.union(iv), 2, 10).tolist() == [[2, 3], [5, 10]]
    assert trace.union(np.zeros((0, 2), np.int64)).shape == (0, 2)


def test_short_names():
    assert trace.short_name("%while.5 = (s32[]) while(...)") == "%while.5"
    assert trace.short_name("jit_wrapper(123)") == "jit_wrapper(123)"


def _synthetic():
    # window [0, 100); device busy [10, 30) (nested ops) and [50, 60)
    ops = [Event("%while.1", 10, 30), Event("%fusion.2", 12, 20),
           Event("%fusion.3", 50, 60), Event("%fusion.4", 150, 160)]
    mods = [Event("jit_wrapper(1)", 10, 30), Event("jit__unknown(2)", 50, 60)]
    host = [Event("bench.window", 0, 100), Event("bench.serve", 0, 45),
            Event("bench.backend", 32, 44), Event("bench.batcher_wait", 62, 99)]
    return {0: {trace.OPS_LINE: ops, trace.MODULES_LINE: mods}}, host


def test_reduce_synthetic():
    devices, host = _synthetic()
    s = trace.reduce(devices, host, MODULES)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_per_device == [pytest.approx(30e-9)]
    assert s.module_s["cache_step"] == [pytest.approx(20e-9)]
    assert s.module_runs["cache_step"] == [1]
    names = dict((n, d) for n, d in s.gaps)
    # gaps: [0,10) in serve, [30,50) mostly backend, [60,100) batcher wait
    assert sorted(s.gaps, key=lambda g: -g[1])[0][0] == "bench.batcher_wait"
    assert names["bench.backend"] == pytest.approx(20e-9)
    assert names["bench.serve"] == pytest.approx(10e-9)
    assert s.top_ops[0] == ("%while.1", pytest.approx(20e-9))
    b = s.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_reduce_needs_the_window_span():
    devices, host = _synthetic()
    with pytest.raises(ValueError):
        trace.reduce(devices, [e for e in host if e.name != "bench.window"], MODULES)


RECORDED = os.path.join(ROOT, "chipbench", "testdata", "v5e_drift_closed.xplane.pb.gz")
EXPECTED = os.path.join(ROOT, "chipbench", "testdata", "v5e_drift_closed.expected.json")


def test_reduce_a_trace_recorded_on_the_chip():
    from jax._src.profiler import ProfileData

    with gzip.open(RECORDED, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    devices, host = trace.planes_of(pd)
    with open(os.path.join(ROOT, "chipbench", "modules.json")) as f:
        s = trace.reduce(devices, host, json.load(f))
    with open(EXPECTED) as f:
        want = json.load(f)
    assert s.window_s == pytest.approx(want["window_s"])
    assert s.busy_s == pytest.approx(want["busy_s"])
    assert 0 < s.busy_s < s.window_s
    # one run of the served step per batch the window served
    assert s.module_runs["cache_step"] == [want["batches"]] == want["cache_step_runs"]
    assert s.module_s["cache_step"] == pytest.approx(want["cache_step_s"])
    assert [n for n, _ in s.top_ops[:3]] == want["top_ops"]
    assert {n for n, _ in s.gaps} <= set(trace.GAP_SPANS) | {"idle"}
