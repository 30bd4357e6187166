"""Largest minus smallest per-chip busy share of the traced window, in
percentage points, closed loop: how unevenly the cell's chips are used
(hash imbalance across shards, work that lands on one chip only). None
on a single chip."""
from chipbench import readers


def read(run):
    t = readers._traced(run, "closed")
    if t is None or len(t.busy_per_device) < 2 or t.window_s <= 0:
        return None
    return 100.0 * (max(t.busy_per_device) - min(t.busy_per_device)) / t.window_s
