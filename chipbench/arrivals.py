"""Seeded arrival processes for the open-loop cells.

A copy of the program's arrival processes (``ArrivalSpec.times`` in its
load generator): Poisson arrivals at a mean rate, and an on-off
Markov-modulated process whose ON state runs at ``burst`` times the mean
rate.  Same seed, same timestamps (held to the original by
``tests/chipbench/test_chipbench_traffic.py``).  Times are seconds after
the start of the window, nondecreasing.
"""
from __future__ import annotations

from typing import List

import numpy as np


def poisson_times(seed, rate: float, n: int) -> np.ndarray:
    """``n`` Poisson arrival times at mean ``rate`` requests per second."""
    if n <= 0:
        return np.zeros(0, np.float64)
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def onoff_times(
    seed, rate: float, n: int, burst: float = 4.0, on_frac: float = 0.2,
    mean_on_s: float = 0.02,
) -> np.ndarray:
    """``n`` arrival times of a two-state MMPP whose long-run rate is
    ``rate``: exponential ON sojourns (mean ``mean_on_s``) at
    ``burst * rate`` alternate with OFF sojourns at the rate that keeps
    the mean; needs ``burst * on_frac <= 1``."""
    if n <= 0:
        return np.zeros(0, np.float64)
    if not (burst >= 1.0 and 0.0 < on_frac < 1.0 and burst * on_frac <= 1.0 + 1e-12):
        raise ValueError(f"onoff needs burst >= 1 and burst * on_frac <= 1, got "
                         f"burst={burst} on_frac={on_frac}")
    rng = np.random.default_rng(seed)
    rate_on = rate * burst
    rate_off = rate * (1.0 - burst * on_frac) / (1.0 - on_frac)
    mean_off = mean_on_s * (1.0 - on_frac) / on_frac
    out: List[np.ndarray] = []
    remaining = n
    t = 0.0
    on = bool(rng.random() < on_frac)
    while remaining > 0:
        dur = float(rng.exponential(mean_on_s if on else mean_off))
        r = rate_on if on else rate_off
        if r > 0 and dur > 0:
            # conditioned on the count, Poisson arrival times in a window
            # are iid uniform
            k = min(int(rng.poisson(r * dur)), remaining)
            if k:
                out.append(t + np.sort(rng.random(k)) * dur)
                remaining -= k
        t += dur
        on = not on
    return np.concatenate(out)


PROCESSES = {"poisson": poisson_times, "onoff": onoff_times}
