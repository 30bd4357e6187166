"""The chip smoke's body at a tiny size on the CPU, plus the entry points'
refusals and compile-cache placement.

``chip_smoke.run`` is what ``python chip_smoke.py`` serves on a TPU; here
it runs the same path with the device engine forced on the CPU backend
and must agree request for request with the host engine and the
backend.  The four-shard case runs in a child process on four virtual
CPU devices (``JAX_PLATFORMS=cpu``, so the child never loads the TPU
library) and checks that shard i's state stays on device i through
serving, a checkpoint restore and ``recover_shard``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")
TINY = dict(entries=2048, requests=40_000, batch=256)


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _child_env(**extra) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(ROOT, "src"), env.get("PYTHONPATH")) if p
    )
    return env


def test_chip_smoke_body_on_cpu():
    smoke = _load_smoke()
    r = smoke.run(batches=4, shards=1, **TINY)
    assert r["batches"] == 4 and r["requests"] == 4 * TINY["batch"]
    assert r["value_mismatches"] == 0
    assert r["hit_mismatches"] == 0
    assert r["degraded"] == 0 and r["failed_over"] == 0
    assert 0.0 < r["hit_rate"] == r["host_hit_rate"] < 1.0
    # full batches: one bucket shape, one device dispatch per batch
    assert r["dispatch_counts"] == {"one_call": 4}
    assert r["trace_counts"] == {"one_call": 1}
    assert smoke.placement_errors(r["placement"], 1) == []
    assert r["state_bytes"] > 0


def test_chip_smoke_four_shards_stay_on_their_devices():
    code = (
        "import json, sys; sys.path.insert(0, sys.argv[1]);"
        "import chip_smoke as s;"
        "r = s.run(entries=2048, requests=40000, batch=256, batches=3, shards=4);"
        "print(json.dumps({k: r[k] for k in ('placement', 'value_mismatches',"
        " 'hit_mismatches', 'degraded', 'batches')}))"
    )
    out = subprocess.run(
        [sys.executable, "-c", code, ROOT],
        env=_child_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["batches"] == 3 + _load_smoke().AFTER_RECOVERY
    assert r["value_mismatches"] == 0 and r["hit_mismatches"] == 0
    assert r["degraded"] == 0
    assert set(r["placement"]) == {
        "served", "restored", "recovered", "served_after_recovery"
    }
    for per_shard in r["placement"].values():
        assert per_shard == [[0], [1], [2], [3]]
    assert _load_smoke().placement_errors(r["placement"], 4) == []


_WARMUP_ON_FOUR_DEVICES = """
import json
import jax
import numpy as np
from repro.core import CacheSpec, VecLog, VecStats
from repro.serving import BucketSpec, Cluster, ServingSpec

rng = np.random.default_rng(8)
keys = rng.integers(0, 500, size=4000)
topic = rng.integers(-1, 4, size=500)
stats = VecStats.from_log(VecLog(keys=keys, n_train=2000, key_topic=topic))
backend = lambda q: np.tile(np.asarray(q)[:, None], (1, 2)).astype(np.int32)
spec = ServingSpec(
    cache=CacheSpec.from_strategy("STDv_LRU", 256, f_s=0.2, f_t=0.6),
    value_dim=2, shards=4, engine="device", microbatch=64,
    bucket=BucketSpec(min_size=8), aot_warmup=True,
)
compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, secs, **_: compiles.append(event)
    if event == "/jax/core/compile/backend_compile_duration" else None
)
with Cluster.from_spec(spec, stats, [backend], value_fn=backend) as c:
    warmed = len(compiles)
    for n in (64, 33, 57, 7, 1, 64):
        c.serve(rng.integers(0, 500, size=n))
    print(json.dumps({"warmed": warmed, "served": len(compiles) - warmed}))
"""


def test_aot_warmup_compiles_on_each_shards_own_device():
    """Warmup runs after placement: a four-shard cluster on four devices
    compiles nothing more once it serves (a warmup on the default device
    would recompile every shape on the shard's own)."""
    out = subprocess.run(
        [sys.executable, "-c", _WARMUP_ON_FOUR_DEVICES],
        env=_child_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["warmed"] > 0 and r["served"] == 0


def test_chip_smoke_refuses_a_cpu_backend():
    out = subprocess.run(
        [sys.executable, SMOKE], env=_child_env(),
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_placement_errors_flags_a_misplaced_shard():
    smoke = _load_smoke()
    assert smoke.placement_errors({"served": [[0], [1]]}, 2) == []
    errors = smoke.placement_errors({"restored": [[0], [0]], "served": [[0, 1]]}, 2)
    assert len(errors) == 2


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_directory(tmp_path, env_dir):
    """Without JAX_COMPILATION_CACHE_DIR the cache sits at the fixed path
    inside the checkout; with it, JAX's own directory is left alone."""
    extra = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if env_dir else {}
    env = _child_env(**extra)
    if not env_dir:
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c",
         "import repro; print(repro.configure_compile_cache())"],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    got = out.stdout.strip().splitlines()[-1]
    assert got == (str(tmp_path) if env_dir else os.path.join(ROOT, ".jax_cache"))
