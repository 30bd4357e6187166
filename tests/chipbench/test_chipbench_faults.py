"""A whole run at a small size on the CPU: sound, it comes out correct;
with the timed path broken underneath, it does not."""
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from repro.serving import cluster as cluster_mod  # noqa: E402
from repro.serving import device_cache  # noqa: E402

SEED = 2**31 + 77


def _cell(loop="closed", shards=1):
    with open(os.path.join(ROOT, "chipbench", "configs", "std_c2_1chip.json")) as f:
        cfg = json.load(f)
    cfg.update(entries=4096, shards=shards)
    traffic = {"generator": "drift", "stream_seed": 11, "shuffle_block": 1024,
               "train_phases": 1, "loop": loop, "max_batch": 256,
               "params": {"n_requests": 400_000, "n_topics": 16,
                          "queries_per_topic": 6_000, "n_notopic_queries": 10_000,
                          "n_phases": 4}}
    # the metrics each loop reports, whichever cells BENCHMARK.json holds
    e2e = [{"name": "throughput_rps", "unit": "req/s"}, {"name": "setup_s", "unit": "s"}]
    suffix = ".closed"
    if loop == "open":
        traffic.update(arrivals={"process": "poisson", "rate": 4000}, deadline_ms=2.0)
        e2e = [{"name": "latency_p50_ms", "unit": "ms"},
               {"name": "latency_p99_ms", "unit": "ms"}, e2e[1]]
        suffix = ".poisson"
    metrics = os.listdir(os.path.join(ROOT, "chipbench", "metrics"))
    per_layer = [{"name": f[:-3], "unit": "-"} for f in sorted(metrics)
                 if f.endswith(suffix + ".py") or f == "compile_s.py"]
    return harness.Cell(f"tiny.{loop}", cfg, traffic, 1, per_layer, e2e)


def _run(cell, traced=False):
    return harness.run_cell(ROOT, cell.name, SEED, 1.0, traced, time.perf_counter(),
                            require_tpu=False, cell=cell)


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_sound_run_is_correct(loop):
    out = _run(_cell(loop))
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["hit_mismatches"] == {"value": 0, "limit": 0}
    names = {m["name"] for m in _cell(loop).end_to_end}
    assert set(out["metrics"]) == names and len(names) >= 2
    assert all(m["value"] > 0 for m in out["metrics"].values())


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_traced_run_reports_host_metrics_and_compile_time(loop):
    out = _run(_cell(loop), traced=True)
    assert out["correct"]
    suffix = "." + ("poisson" if loop == "open" else loop)
    for name in ("backend_ms" + suffix, "broker_ms" + suffix, "compile_s"):
        assert out["metrics"][name]["value"] > 0
    if loop == "open":
        assert out["metrics"]["loadgen_late_p99_ms.poisson"]["value"] >= 0
    assert "breakdown" in out and "window_s" in out["device"]


def _state_unchanged(monkeypatch):
    orig = device_cache.STDDeviceCache.serve_one_call

    def step(self, state, *a, **k):
        out = orig(self, state, *a, **k)
        return out[:4] + (state,) + out[5:]

    monkeypatch.setattr(device_cache.STDDeviceCache, "serve_one_call", step)


def _answer_altered(monkeypatch):
    orig = device_cache.STDDeviceCache.serve_one_call

    def step(self, *a, **k):
        out = list(orig(self, *a, **k))
        out[2] = out[2] + (out[1] == 1)[:, None].astype(out[2].dtype)
        return tuple(out)

    monkeypatch.setattr(device_cache.STDDeviceCache, "serve_one_call", step)


def _half_batch(monkeypatch):
    orig = cluster_mod.Cluster.serve

    def serve(self, q):
        half = len(q) // 2
        v, h = orig(self, q[:half])
        return (np.concatenate([v, np.zeros((len(q) - half, v.shape[1]), v.dtype)]),
                np.concatenate([h, np.zeros(len(q) - half, bool)]))

    monkeypatch.setattr(cluster_mod.Cluster, "serve", serve)


def _exchange_left_out(monkeypatch):
    orig = cluster_mod.Cluster.serve

    def serve(self, q):
        v, h = orig(self, q)
        away = self.spec.shard_of(q) != 0
        v, h = v.copy(), h.copy()
        v[away], h[away] = 0, False
        return v, h

    monkeypatch.setattr(cluster_mod.Cluster, "serve", serve)


@pytest.mark.parametrize("fault,shards,check", [
    (_state_unchanged, 1, "hit_mismatches"),
    (_answer_altered, 1, "value_mismatches"),
    (_half_batch, 1, "value_mismatches"),
    (_exchange_left_out, 4, "value_mismatches"),
])
def test_broken_path_is_not_correct(monkeypatch, fault, shards, check):
    fault(monkeypatch)
    out = _run(_cell("closed", shards))
    assert not out["correct"]
    assert out["checks"][check]["value"] > 0
    assert out["failed"] > 0


def test_control_in_the_programs_place_is_not_correct():
    """The control (the reference with FIFO sets) against the same
    served sequence: it fails the hit mask, the program does not."""
    cell = _cell("closed")
    setup = harness.set_up(cell, SEED)
    res, _ = harness.measure(setup, SEED, 1.0, False, "")
    setup.cluster.close()
    program, failed = harness.check(setup, res)
    control, control_failed = harness.check(setup, res, control=True)
    assert failed == 0 and program["hit_mismatches"]["value"] == 0
    assert control["hit_mismatches"]["value"] > 0 and control_failed > 0
    assert control["value_mismatches"]["value"] == 0
