"""BENCHMARK.json is whole: every cell's configuration, traffic mix and
per-layer reader exists as a file found by name; and run.py refuses a
host without a TPU, an unknown chip, and a checkout without the program."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_names_and_files():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert NAME.match(c["name"]) and os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "traffic", w["traffic"] + ".json"))
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(CELLS) // 2)
    for m in BENCH["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "metrics", m["name"] + ".py"))
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_and_reports_setup_and_another_metric(cell):
    c = harness.load_cell(ROOT, cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    assert c.config["chips"] == c.chips
    assert c.traffic["loop"] in ("closed", "open")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0], "--seed",
         str(2**31 + 9), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_host_without_a_tpu():
    r = _run(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_run_refuses_a_checkout_with_the_benchmark_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert r.stdout.strip() == ""


class _Device:
    platform = "tpu"
    device_kind = "TPU v99"


def test_unknown_chip_is_refused(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: [_Device()])
    with pytest.raises(harness.CellError, match="peaks.json"):
        harness.check_devices(1, require_tpu=True)
    with pytest.raises(harness.CellError, match="chips"):
        monkeypatch.setattr(jax, "devices", lambda: [type("D", (_Device,), {"device_kind": "TPU v5 lite"})()])
        harness.check_devices(4, require_tpu=True)
