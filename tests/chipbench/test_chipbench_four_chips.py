"""The cell std4.drift.closed (``chipbench/configs/std_c2_4chip.json``
under the ``drift_closed`` mix) on four virtual CPU devices, through the
benchmark's own harness: a whole run, cut to 4096 entries and a short
stream, comes out correct while its control does not, and the device
engine on four shards agrees with the reference on ragged batches. Its
two per-chip readers are worked by hand on a synthetic trace summary."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness, trace  # noqa: E402

CELL = "std4.drift.closed"

# the deployment as a cell, cut to a CPU's size; argv: root, seed
_TINY_CELL = r"""
import json, os, sys, time
sys.path.insert(0, sys.argv[1])
from chipbench import harness

root, seed = sys.argv[1], int(sys.argv[2])
cell = harness.load_cell(root, "std4.drift.closed")
cell.config.update(entries=4096)
cell.traffic.update(shuffle_block=1024, max_batch=256)
cell.traffic["params"].update(n_requests=400_000, queries_per_topic=6_000,
                              n_notopic_queries=10_000, n_phases=4)
"""

_RUN = _TINY_CELL + r"""
import jax
out = harness.run_cell(root, cell.name, seed, 1.0, False, time.perf_counter(),
                       require_tpu=False, cell=cell)
setup = harness.set_up(cell, seed)
res, _ = harness.measure(setup, seed, 0.5, False, "")
devices = jax.devices()
placed = [[devices.index(d) for d in b.state["ks"].devices()]
          for b in setup.cluster.brokers]
stats = setup.cluster.stats
setup.cluster.close()
program, _ = harness.check(setup, res)
control, _ = harness.check(setup, res, control=True)
print(json.dumps({
    "out": out, "placed": placed, "slice_max_requests": stats.slice_max_requests,
    "requests": stats.requests, "batches": stats.batches,
    "program": {k: v["value"] for k, v in program.items()},
    "control": {k: v["value"] for k, v in control.items()},
}))
"""

_RAGGED = _TINY_CELL + r"""
import numpy as np
from chipbench import reference

cfg, stream = cell.config, harness.streams.make(cell.traffic, seed,
                                                topic_pipeline=harness._topic_pipeline)
backend = lambda q: np.repeat(np.asarray(q, np.int32)[:, None], cfg["value_dim"], 1)
rng = np.random.default_rng(seed % 2**32)
sizes = rng.integers(1, 2049, size=80)
buckets = set()
with harness._build_cluster(cfg, cell.traffic | {"max_batch": 2048}, stream, backend) as c:
    hits, lo = [], 0
    for n in sizes:
        q = stream.served[lo : lo + n]
        slices = np.bincount(c.spec.shard_of(q), minlength=4)
        buckets.update(c.brokers[0].bucket.padded_len(int(k)) for k in slices if k)
        _, h = c.serve(q)
        hits.append(h)
        lo += n
    padded = c.stats.padded
got = np.concatenate(hits)
layout = reference.build_layout(stream.keys[: stream.n_train], stream.key_topic,
                                cfg["entries"], cfg["f_s"], cfg["f_t"], cfg["f_ts"],
                                cfg["ways"], cfg["shards"])
keys = stream.served[: sizes.sum()]
want = reference.replay(layout, keys, stream.key_topic, sizes)
fifo = reference.replay(layout, keys, stream.key_topic, sizes, refresh=False)
print(json.dumps({"mismatches": int((got != want).sum()), "hit_rate": float(want.mean()),
                  "fifo_mismatches": int((fifo != got).sum()), "requests": int(sizes.sum()),
                  "buckets": sorted(buckets), "padded": int(padded)}))
"""


def _on_four_devices(script, seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(ROOT, "src"), env.get("PYTHONPATH")) if p)
    out = subprocess.run([sys.executable, "-c", script, ROOT, str(seed)], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cell_is_correct_on_four_devices_and_its_control_is_not():
    r = _on_four_devices(_RUN, 2**31 + 93)
    out = r["out"]
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["hit_mismatches"] == {"value": 0, "limit": 0}
    assert out["checks"]["value_mismatches"] == {"value": 0, "limit": 0}
    assert out["device"]["count"] == 4
    assert set(out["metrics"]) == {"throughput_rps", "setup_s"}
    assert r["placed"] == [[0], [1], [2], [3]]  # shard i on device i
    assert r["batches"] > 0
    assert r["requests"] / 4 < r["slice_max_requests"] < r["requests"]
    assert r["program"] == {"hit_mismatches": 0, "value_mismatches": 0}
    assert r["control"]["hit_mismatches"] > 0
    assert r["control"]["value_mismatches"] == 0


def test_device_engine_on_four_shards_matches_the_reference():
    """Ragged batches up to 2048: shard slices land in most buckets of
    the ladder, on both sides of a quarter batch, with pads, and the hit
    mask is the reference's."""
    r = _on_four_devices(_RAGGED, 2**31 + 7)
    assert 0.1 < r["hit_rate"] < 0.9
    assert r["mismatches"] == 0
    assert r["fifo_mismatches"] > 0
    assert {256, 512, 1024} <= set(r["buckets"]) and len(r["buckets"]) >= 5
    assert r["padded"] > 0


def test_cell_is_listed_with_its_configuration_mix_and_metrics():
    c = harness.load_cell(ROOT, CELL)
    assert (c.chips, c.config["name"], c.config["shards"]) == (4, "std_c2_4chip", 4)
    assert c.traffic == harness._load_json(
        os.path.join(ROOT, "chipbench", "traffic", "drift_closed.json"))
    assert {m["name"] for m in c.end_to_end} == {"throughput_rps", "setup_s"}
    assert {m["name"] for m in c.per_layer} == {
        "cache_step_ms.closed", "cache_step_roofline.closed", "device_idle_share.closed",
        "backend_ms.closed", "broker_ms.closed", "compile_s",
        "cache_step_max_ms.closed", "chip_busy_spread.closed"}
    for one_chip in ("std1.drift.closed", "std1.calib.closed"):
        names = {m["name"] for m in harness.load_cell(ROOT, one_chip).per_layer}
        assert not names & {"cache_step_max_ms.closed", "chip_busy_spread.closed"}


def _summary(busy, step):
    return trace.Summary(window_s=10.0, busy_per_device=busy,
                         module_s={"cache_step": step},
                         module_runs={"cache_step": [100] * len(step)}, top_ops=[], gaps=[])


def _record(summary, loop="closed", batches=100):
    return dict(loop=loop, batches=batches, trace=summary)


@pytest.mark.parametrize("name, busy, step, want", [
    # the busiest chip's step: 1.2 s over 100 batches
    ("cache_step_max_ms.closed", [1.0, 1.5, 0.75, 2.0], [0.5, 0.8, 0.4, 1.2], 12.0),
    ("cache_step_max_ms.closed", [1.0], [0.3], 3.0),
    # busiest 2.0 s less idlest 0.75 s of a 10 s window
    ("chip_busy_spread.closed", [1.0, 1.5, 0.75, 2.0], [0.5, 0.8, 0.4, 1.2], 12.5),
    ("chip_busy_spread.closed", [2.0, 2.0, 2.0, 2.0], [0.5, 0.5, 0.5, 0.5], 0.0),
    ("chip_busy_spread.closed", [1.0], [0.3], None),  # no spread on one chip
])
def test_per_chip_readers_by_hand(name, busy, step, want):
    got = harness._load_reader(name)(_record(_summary(busy, step)))
    assert got == pytest.approx(want) if want is not None else got is None


@pytest.mark.parametrize("name", ["cache_step_max_ms.closed", "chip_busy_spread.closed"])
def test_per_chip_readers_read_nothing_without_a_closed_loop_trace(name):
    read = harness._load_reader(name)
    s = _summary([1.0, 2.0], [0.5, 0.6])
    assert read(_record(None)) is None
    assert read(_record(s, loop="open")) is None
    assert read(_record(_summary([], []))) is None
