"""The roofline's byte count reads the configuration's widths only, and
the table of peaks carries its source."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import roofline  # noqa: E402

CONFIGS = [os.path.join(ROOT, "chipbench", "configs", f)
           for f in sorted(os.listdir(os.path.join(ROOT, "chipbench", "configs")))]


def test_count_at_the_benchmark_widths():
    cfg = {"ways": 8, "value_dim": 8}
    # set words 2 x 4W x 4 B, static key 8 B, value row 32 B, inputs 13 B,
    # outputs 33 B; an insert writes one more value row
    assert roofline.step_bytes(cfg, 1, 0) == 256 + 8 + 32 + 13 + 33
    assert roofline.step_bytes(cfg, 4096, 100) == 4096 * 342 + 100 * 32


@pytest.mark.parametrize("path", CONFIGS)
def test_count_reads_only_the_widths(path):
    with open(path) as f:
        cfg = json.load(f)
    base = roofline.step_bytes(cfg, 4096, 1000)
    only_widths = {"ways": cfg["ways"], "value_dim": cfg["value_dim"]}
    assert roofline.step_bytes(only_widths, 4096, 1000) == base
    scaled = dict(cfg, entries=cfg["entries"] * 16, shards=8, f_s=0.1, f_t=0.8,
                  f_ts=0.9, strategy="SDC", routing="topic")
    assert roofline.step_bytes(scaled, 4096, 1000) == base
    assert roofline.step_bytes(dict(cfg, ways=cfg["ways"] * 2), 4096, 1000) > base
    assert roofline.step_bytes(dict(cfg, value_dim=cfg["value_dim"] * 2), 4096, 1000) > base


def test_share_of_the_roofline():
    cfg = {"ways": 8, "value_dim": 8}
    b = roofline.step_bytes(cfg, 4096, 0)
    assert roofline.roofline_share(cfg, 4096, 0, b / 819e9, 819e9) == pytest.approx(100.0)
    assert roofline.roofline_share(cfg, 4096, 0, 10 * b / 819e9, 819e9) == pytest.approx(10.0)


def test_peaks_table_has_v5e_with_its_source():
    with open(os.path.join(ROOT, "chipbench", "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    for kind, row in peaks.items():
        assert "source" in row and row["source"]
