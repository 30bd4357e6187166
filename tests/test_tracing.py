"""The serving layers' own spans and counters, and the names of their
compiled programs.

A small device-engine cluster serves a few batches under
``jax.profiler`` on the CPU; its ``cluster.*``/``broker.*`` spans are
read back from the trace's ``/host:CPU`` plane and must form the
documented tree, one ``broker.serve`` per shard slice, every span of a
batch carrying the batch's ``seq``.  ``BrokerStats`` must count seven
readbacks, ten uploads and seven copies started at dispatch per one-call
batch (every copy asked for before the batch's first fetch), the
conflict replay's
depth exactly as ``ops.plan_segments`` gives it, and the lane-rounds
``ops.replay_schedule`` runs at that depth.  The four-shard case
runs in a child process on four virtual CPU devices.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CacheSpec, VecLog, VecStats
from repro.kernels.cache_ops.ops import plan_segments, replay_lane_rounds
from repro.serving import (
    Broker,
    BucketSpec,
    Cluster,
    ServingSpec,
    pack_hashes,
    splitmix64,
)
from repro.serving.device_cache import pad_batch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BATCH = 64
with open(os.path.join(ROOT, "chipbench", "configs", "std_c2_1chip.json")) as _f:
    ARCH = json.load(_f)["backend_arch"]  # the benchmark's miss backend
PHASES = ["broker.prepare", "broker.dispatch", "broker.readback", "broker.miss",
          "broker.fill_plan"]

# the cluster and the spans it leaves in a trace; run here and, with
# four virtual devices, in a child process
_TRACED_SERVE = r"""
import glob, json, os, sys
import jax
import numpy as np
from jax._src.profiler import ProfileData
from repro.core import CacheSpec, VecLog, VecStats
from repro.serving import BucketSpec, Cluster, ServingSpec


def traced_serve(shards, trace_dir, batches=4, batch=64):
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 2000, size=8000)
    topic = rng.integers(-1, 4, size=2000)
    stats = VecStats.from_log(VecLog(keys=keys, n_train=4000, key_topic=topic))
    backend = lambda q: np.tile(np.asarray(q)[:, None], (1, 2)).astype(np.int32)
    spec = ServingSpec(
        cache=CacheSpec.from_strategy("STDv_LRU", 512, f_s=0.2, f_t=0.6),
        value_dim=2, shards=shards, engine="device", microbatch=batch,
        bucket=BucketSpec(min_size=8),
    )
    with Cluster.from_spec(spec, stats, [backend], value_fn=backend) as c:
        c.warmup()  # nothing compiles inside the trace
        c.serve(keys[4000 : 4000 + batch])
        s0 = c.stats
        jax.profiler.start_trace(trace_dir)
        try:
            for k in range(1, batches + 1):
                c.serve(keys[4000 + k * batch : 4000 + (k + 1) * batch])
        finally:
            jax.profiler.stop_trace()
        s1 = c.stats
    pd = ProfileData.from_file(
        sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))[-1])
    spans = [  # name, start, end, thread (line), metadata
        [e.name, int(e.start_ns), int(e.start_ns + e.duration_ns), line,
         {k: v for k, v in e.stats}]
        for pl in pd.planes if pl.name == "/host:CPU"
        for line, ln in enumerate(pl.lines) for e in ln.events
        if e.name.startswith(("cluster.", "broker."))
    ]
    counts = {f: getattr(s1, f) - getattr(s0, f)
              for f in ("readbacks", "uploads", "early_copies", "replay_rounds",
                        "batches")}
    return sorted(spans, key=lambda s: (s[1], -s[2])), counts


if __name__ == "__main__":
    print(json.dumps(traced_serve(int(sys.argv[1]), sys.argv[2])))
"""


def _traced_serve(shards, trace_dir):
    ns: dict = {}
    exec(compile(_TRACED_SERVE, "traced_serve", "exec"), ns)
    return ns["traced_serve"](shards, trace_dir)


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _check_tree(spans, shards):
    """Every batch: one cluster.serve holding its cluster.route, one
    broker.serve per non-empty shard slice, each holding the phases in
    order, and with more than one shard one cluster.gather in the
    front end's thread, from a slice's completion to the last slice's;
    every span of the batch carries its seq."""
    roots = [s for s in spans if s[0] == "cluster.serve"]
    assert [r[4]["seq"] for r in roots] == list(range(1, len(roots) + 1))
    for root in roots:
        seq = root[4]["seq"]
        assert root[4]["requests"] == BATCH
        mine = [s for s in spans if s[4].get("seq") == seq]
        assert all(_inside(s, root) for s in mine)
        assert [s[0] for s in mine].count("cluster.route") == 1
        serves = [s for s in mine if s[0] == "broker.serve"]
        assert 1 <= len(serves) <= shards
        assert len({s[4]["shard"] for s in serves}) == len(serves)
        assert sum(s[4]["b"] for s in serves) == BATCH
        flushes = 0
        for bs in serves:
            assert bs[4]["bp"] >= bs[4]["b"]
            phases = [s[0] for s in mine
                      if s[3] == bs[3] and _inside(s, bs) and s is not bs]
            # a standalone fill, where the last plan outgrew this bucket
            if "broker.flush" in phases:
                assert phases.index("broker.flush") == 1
                phases.remove("broker.flush")
                flushes += 1
            assert phases == PHASES
        gathers = [s for s in mine if s[0] == "cluster.gather"]
        assert len(gathers) == (shards > 1)
        for g in gathers:
            assert g[3] == root[3]  # the front end's thread
            assert g[4]["last_shard"] in {s[4]["shard"] for s in serves}
            # opens once a slice has come back, closes after the last one
            assert min(s[2] for s in serves) <= g[1]
            assert max(s[2] for s in serves) <= g[2]
        # no other program span belongs to the batch
        assert len(mine) == (
            2 + len(gathers) + len(serves) * (1 + len(PHASES)) + flushes)
    return roots


def test_spans_form_the_tree_on_one_shard(tmp_path):
    spans, counts = _traced_serve(1, str(tmp_path))
    roots = _check_tree(spans, 1)
    assert len(roots) == counts["batches"] == 4


@pytest.fixture(scope="module")
def four_shards(tmp_path_factory):
    """Spans and counter deltas of the traced serve on four shards, run
    in a child process on four virtual CPU devices."""
    tmp = tmp_path_factory.mktemp("four_shards")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(ROOT, "src"), env.get("PYTHONPATH")) if p)
    script = tmp / "traced_serve.py"
    script.write_text(_TRACED_SERVE)
    out = subprocess.run([sys.executable, str(script), "4", str(tmp / "t4")],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    spans4, counts = json.loads(out.stdout.strip().splitlines()[-1])
    return [tuple(s) for s in spans4], counts


def test_four_shards_report_the_same_span_names(tmp_path, four_shards):
    spans4, _ = four_shards
    roots = _check_tree(spans4, 4)
    assert len(roots) == 4
    # the slices of a batch run on the shards' own threads
    assert max(len([s for s in spans4 if s[0] == "broker.serve" and s[4]["seq"] == r[4]["seq"]])
               for r in roots) > 1
    spans1, _ = _traced_serve(1, str(tmp_path / "t1"))
    # full batches on one shard never outgrow their bucket, so never
    # flush; one shard has no gather
    assert "cluster.gather" in {s[0] for s in spans4}
    assert ({s[0] for s in spans4} - {"broker.flush", "cluster.gather"}
            == {s[0] for s in spans1})


def test_counters_per_one_call_batch(tmp_path):
    _, counts = _traced_serve(1, str(tmp_path))
    assert counts["batches"] == 4
    assert counts["readbacks"] == 7 * 4
    assert counts["uploads"] == 10 * 4
    assert counts["early_copies"] == 7 * 4
    assert counts["replay_rounds"] >= 4


def test_counters_per_one_call_batch_on_four_shards(four_shards):
    spans, counts = four_shards
    slices = counts["batches"]  # summed over the shards: one per served slice
    assert slices > 4
    flushes = sum(s[0] == "broker.flush" for s in spans)  # four uploads each
    assert counts["readbacks"] == 7 * slices
    assert counts["early_copies"] == 7 * slices
    assert counts["uploads"] == 10 * slices + 4 * flushes


def _record_transfers(monkeypatch):
    """Record, in order, every copy to the host asked of a device array
    and every ``Broker._fetch`` of one, as ``(kind, id(array))``."""
    events = []
    array_type = type(jnp.zeros(1))
    start, fetch = array_type.copy_to_host_async, Broker._fetch

    def recorded_copy(x):
        events.append(("copy", id(x)))
        return start(x)

    def recorded_fetch(self, x, copy=False):
        if isinstance(x, jax.Array):
            events.append(("fetch", id(x)))
        return fetch(self, x, copy)

    monkeypatch.setattr(array_type, "copy_to_host_async", recorded_copy)
    monkeypatch.setattr(Broker, "_fetch", recorded_fetch)
    return events


@pytest.mark.parametrize("path, copies, fetches", [
    ({}, 7, (5, 7)),  # one call; five fetches where the batch wrote nothing
    ({"fused_one_call": False}, 7, (5, 7)),  # the legacy fused pair
    ({"fused": False}, 4, (4,)),  # unfused: the probe's outputs
])
def test_copies_start_at_dispatch_before_the_first_fetch(monkeypatch, path, copies,
                                                         fetches):
    broker, stream = _broker(**path)
    with broker:
        events = _record_transfers(monkeypatch)
        for lo in range(0, 6 * 50, 50):  # ragged: 50 requests pad to 64
            before = broker.stats.early_copies
            broker.serve(stream[lo : lo + 50])
            kinds = [k for k, _ in events]
            assert kinds[:copies] == ["copy"] * copies
            assert kinds[copies:] == ["fetch"] * (len(kinds) - copies)
            assert len(kinds) - copies in fetches
            started = {i for k, i in events if k == "copy"}
            assert len(started) == copies  # distinct arrays
            assert {i for k, i in events if k == "fetch"} <= started
            assert broker.stats.early_copies - before == copies
            events.clear()


def _broker(engine="device", microbatch=BATCH, **kw):
    """A broker on one shard's worth of a small stream, and the stream's
    measured half; ``kw`` are further ``ServingSpec`` fields."""
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 3000, size=12000)
    topic = rng.integers(-1, 4, size=3000)
    stats = VecStats.from_log(VecLog(keys=keys, n_train=6000, key_topic=topic))
    backend = lambda q: np.tile(np.asarray(q)[:, None], (1, 2)).astype(np.int32)  # noqa: E731
    spec = ServingSpec(
        cache=CacheSpec.from_strategy("STDv_LRU", 512, f_s=0.2, f_t=0.6),
        value_dim=2, engine=engine, microbatch=microbatch,
        bucket=BucketSpec(min_size=8) if engine == "device" else BucketSpec(mode="none"),
        **kw,
    )
    return Broker.from_spec(spec, stats, [backend], value_fn=backend), keys[6000:]


def test_replay_rounds_is_the_depth_of_plan_segments():
    broker, stream = _broker()
    want = 0
    with broker:
        for lo in range(0, 10 * 50, 50):  # ragged: 50 requests pad to 64
            q = stream[lo : lo + 50]
            h_hi, h_lo = pack_hashes(splitmix64(q))
            parts = np.asarray(broker.cache.parts_for(broker.topic_of(q)), np.int32)
            bp = broker.bucket.padded_len(len(q))
            assert bp == 64
            h_hi, h_lo, parts, _, _ = pad_batch(h_hi, h_lo, parts, broker.cache.k, bp)
            set_idx = broker.cache._set_index(jnp.asarray(h_lo), jnp.asarray(parts))
            before = broker.stats.admitted
            broker.serve(q)
            assert broker.stats.admitted > before  # the batch wrote
            want += int(jnp.max(plan_segments(set_idx)[3]))
        assert broker.stats.replay_rounds == want > 10


def _depth(broker, q):
    """The padded length and the replay depth of the batch ``q``, as
    ``Broker.serve`` pads and places it."""
    h_hi, h_lo = pack_hashes(splitmix64(q))
    parts = np.asarray(broker.cache.parts_for(broker.topic_of(q)), np.int32)
    bp = broker.bucket.padded_len(len(q))
    h_hi, h_lo, parts, _, _ = pad_batch(h_hi, h_lo, parts, broker.cache.k, bp)
    set_idx = broker.cache._set_index(jnp.asarray(h_lo), jnp.asarray(parts))
    return bp, int(jnp.max(plan_segments(set_idx)[3]))


def test_replay_lane_rounds_is_the_schedules_count():
    broker, stream = _broker(microbatch=4096)
    want = 0
    with broker:
        hot = np.full(700, stream[0])  # one set 700 deep inside a 4096 batch
        for q in (stream[:50], stream[50:350], np.concatenate([hot, stream[350:3746]])):
            bp, depth = _depth(broker, q)
            before = broker.stats.admitted
            broker.serve(q)
            assert broker.stats.admitted > before  # the batch wrote
            want += replay_lane_rounds(bp, depth)
        assert broker.stats.replay_lane_rounds == want
        assert want < 4096 * 700


def test_replay_lane_rounds_is_bp_times_rounds_up_to_128():
    broker, stream = _broker()
    with broker:
        for lo in range(0, 6 * 50, 50):  # ragged: 50 requests pad to 64
            broker.serve(stream[lo : lo + 50])
        assert broker.stats.replay_rounds > 6
        assert broker.stats.replay_lane_rounds == 64 * broker.stats.replay_rounds


def test_replay_lane_rounds_narrows_one_deep_segment_at_4096():
    broker, stream = _broker(microbatch=4096)
    with broker:
        broker.serve(np.full(4096, stream[0]))  # one set holds the batch
        assert broker.stats.replay_rounds == 4096
        assert broker.stats.replay_lane_rounds == replay_lane_rounds(4096, 4096)
        assert broker.stats.replay_lane_rounds < 4096 * broker.stats.replay_rounds


def test_counters_stay_out_of_warm_up():
    broker, _ = _broker()
    with broker:
        broker.warmup()
        assert broker.stats.uploads == broker.stats.readbacks == 0
        assert broker.stats.early_copies == 0


def test_host_engine_fetches_nothing_from_a_device():
    broker, stream = _broker("host")
    with broker:
        for lo in range(0, 3 * BATCH, BATCH):
            broker.serve(stream[lo : lo + BATCH])
        assert broker.stats.readbacks == broker.stats.uploads == 0
        assert broker.stats.early_copies == 0
        assert broker.stats.replay_rounds >= 3


# -- names of the compiled programs ------------------------------------------


def _entry_args(broker, bp=16):
    h_hi, h_lo = pack_hashes(splitmix64(np.arange(bp)))
    req = [jnp.asarray(a) for a in (h_hi, h_lo, np.zeros(bp, np.int32),
                                     np.ones(bp, bool), np.zeros(bp, np.uint32),
                                     np.zeros(bp, np.uint32))]
    plan = [jnp.asarray(a) for a in broker._pad_plan(None, bp)]
    return req, plan


def test_entries_compile_under_distinct_names_that_cache_step_matches():
    broker, _ = _broker()
    with broker:
        req, plan = _entry_args(broker)
        lowered = {
            "one_call": broker._one_call_step.__wrapped__.lower(broker.state, *plan, *req),
            "fused": broker._fused_step.__wrapped__.lower(broker.state, *req),
            "fused_fill": broker._fused_fill_step.__wrapped__.lower(broker.state, *plan, *req),
            "fill": broker._fill.__wrapped__.lower(broker.state, *plan),
            "probe": broker._probe.__wrapped__.lower(broker.state, *req[:3], req[5]),
        }
        with open(os.path.join(ROOT, "chipbench", "modules.json")) as f:
            cache_step = [re.compile(p) for p in json.load(f)["cache_step"]]
        for entry, low in lowered.items():
            name = f"jit_wrapper.{entry}"
            assert low.as_text().startswith(f"module @{name} "), entry
            assert any(p.search(name) for p in cache_step), entry
        hlo = lowered["one_call"].compile().as_text()
        assert hlo.startswith("HloModule jit_wrapper.one_call,")


def test_one_call_program_is_unchanged_but_for_its_name():
    broker, _ = _broker()
    with broker:
        req, plan = _entry_args(broker)
        named = broker._one_call_step.__wrapped__
        traced = named.__wrapped__  # the trace-counting body, named wrapper.one_call

        def wrapper(*args):
            return traced(*args)

        plain = jax.jit(wrapper).lower(broker.state, *plan, *req).as_text()
        text = named.lower(broker.state, *plan, *req).as_text()
        assert text != plain
        assert text.replace("@jit_wrapper.one_call ", "@jit_wrapper ", 1) == plain


def test_backend_scorer_has_a_name():
    from repro.launch import serve

    backend = serve.build_backend(ARCH, 3, chunk=8)
    jitted = [c.cell_contents for c in backend.__closure__
              if hasattr(c.cell_contents, "lower")]
    assert [j.__name__ for j in jitted] == ["backend_scores"]  # jit_backend_scores


# -- checkpoints from before the counters ----------------------------------


_NEW_COUNTERS = ("readbacks", "uploads", "replay_rounds", "replay_lane_rounds",
                 "early_copies")


def test_checkpoint_without_the_new_counters_restores_them_as_zero():
    broker, stream = _broker()
    with broker, tempfile.TemporaryDirectory() as d:
        for lo in range(0, 4 * BATCH, BATCH):
            broker.serve(stream[lo : lo + BATCH])
        assert all(getattr(broker.stats, k) for k in _NEW_COUNTERS)
        broker.save(d, 1)
        npz = os.path.join(d, "step_0000000001", "arrays.npz")
        arrays = dict(np.load(npz))
        for k in _NEW_COUNTERS:
            del arrays[f"stats/{k}"]
        np.savez(npz, **arrays)
        fresh, _ = _broker()
        with fresh:
            fresh.serve(stream[:BATCH])  # nonzero counters before the restore
            assert fresh.restore(d) == 1
            got, want = dataclasses.asdict(fresh.stats), dataclasses.asdict(broker.stats)
            for k in _NEW_COUNTERS:
                assert got.pop(k) == 0
                want.pop(k)
            assert got == want
