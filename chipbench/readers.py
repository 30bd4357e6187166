"""Shared arithmetic of the per-layer metric readers in ``metrics/``.

Each reader gets the run's record (a dict the harness fills: loop type,
configuration, per-batch host times, per-request times, counters and,
in a traced run, the trace summary) and returns a number, or None where
the run holds nothing for it to read.
"""
from __future__ import annotations

import numpy as np

from . import roofline


def _traced(run, loop):
    return run["trace"] if run["loop"] == loop and run["trace"] is not None else None


def step_ms(run, loop, group="cache_step"):
    """Device milliseconds of a program group per served batch, per device."""
    t = _traced(run, loop)
    if t is None or not run["batches"] or not sum(t.module_runs.get(group, [])):
        return None
    return 1e3 * float(np.mean(t.module_s[group])) / run["batches"]


def step_roofline(run, loop):
    """Percent of the HBM roofline the cache step reached over the window."""
    t = _traced(run, loop)
    if t is None or run["peak"] is None or not sum(t.module_runs.get("cache_step", [])):
        return None
    device_s = float(sum(t.module_s["cache_step"]))
    return roofline.roofline_share(run["config"], run["requests"], run["inserts"],
                                   device_s, run["peak"]["hbm_bytes_per_s"])


def idle_share(run, loop):
    """Percent of the traced window in which no operation ran, mean over
    the cell's devices."""
    t = _traced(run, loop)
    if t is None or not t.busy_per_device or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def backend_ms(run, loop):
    if run["loop"] != loop or not run["batches"]:
        return None
    return 1e3 * float(np.sum(run["backend_s"])) / run["batches"]


def broker_ms(run, loop):
    if run["loop"] != loop or not run["batches"]:
        return None
    return 1e3 * float(np.sum(run["serve_s"]) - np.sum(run["backend_s"])) / run["batches"]


def late_p99_ms(run, loop):
    if run["loop"] != loop or not len(run["late_s"]):
        return None
    return 1e3 * float(np.percentile(run["late_s"], 99))
