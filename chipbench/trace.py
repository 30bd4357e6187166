"""Reduce a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
In it every TPU is a plane named ``/device:TPU:<i>`` whose ``XLA Ops``
line holds one event per operation run on the device and whose
``XLA Modules`` line holds one event per compiled program run; the host
is the plane ``/host:CPU``, one line per thread, where the benchmark's
own spans (``bench.window``, ``bench.serve``, ``bench.backend``,
``bench.batcher_wait``) appear by name.  All share one clock.

From these, within the ``bench.window`` span:

* busy time per device: the union of its operations' intervals;
* device time per program, matched by name through ``modules.json``;
* the idle gaps between busy intervals, each named by the innermost
  benchmark span that covers most of it (``idle`` where none does);
* the operations that took most device time (an operation that runs
  others, such as a ``while`` loop, counts their time too).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
#: host spans that can name an idle gap, innermost first
GAP_SPANS = ("bench.backend", "bench.batcher_wait", "bench.serve")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: int
    end_ns: int


@dataclasses.dataclass
class Summary:
    window_s: float
    #: per device: seconds in which an operation ran
    busy_per_device: List[float]
    #: per program group (modules.json): seconds per device, runs per device
    module_s: Dict[str, List[float]]
    module_runs: Dict[str, List[int]]
    #: (name, seconds) of the operations that took most device time
    top_ops: List[Tuple[str, float]]
    #: (host span or "idle", seconds) of the longest idle gaps, all devices
    gaps: List[Tuple[str, float]]

    @property
    def busy_s(self) -> float:
        return float(np.mean(self.busy_per_device)) if self.busy_per_device else 0.0

    def breakdown(self) -> dict:
        return {
            "device_ops": [[n, s] for n, s in self.top_ops[:10]],
            "idle_gaps": [[n, s] for n, s in self.gaps[:10]],
        }


def union(intervals: np.ndarray) -> np.ndarray:
    """Merge ``(n, 2)`` [start, end) intervals into disjoint sorted ones."""
    if not len(intervals):
        return np.zeros((0, 2), np.int64)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:] - 1, len(iv) - 1)
    return np.stack([starts, ends[last]], axis=1)


def clip(intervals: np.ndarray, lo: int, hi: int) -> np.ndarray:
    if not len(intervals):
        return intervals
    iv = np.stack([np.maximum(intervals[:, 0], lo), np.minimum(intervals[:, 1], hi)], 1)
    return iv[iv[:, 1] > iv[:, 0]]


def short_name(name: str) -> str:
    """An HLO operation's name without its text (``%while.5 = (...)``)."""
    return name.split(" = ", 1)[0] if name.startswith("%") else name


def _events(line) -> List[Event]:
    return [Event(short_name(e.name), int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def planes(path: str):
    """(device events by device index and line name, host span events)."""
    from jax._src.profiler import ProfileData

    return planes_of(ProfileData.from_file(path))


def planes_of(pd):
    devices: Dict[int, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for pl in pd.planes:
        m = DEVICE_PLANE.match(pl.name)
        if m:
            devices[int(m.group(1))] = {
                ln.name: _events(ln) for ln in pl.lines
                if ln.name in (OPS_LINE, MODULES_LINE)
            }
        elif pl.name == HOST_PLANE:
            for ln in pl.lines:
                host.extend(e for e in _events(ln) if e.name.startswith("bench."))
    return devices, host


def _name_gap(lo: int, hi: int, spans: Dict[str, np.ndarray]) -> str:
    for name in GAP_SPANS:
        iv = spans.get(name)
        if iv is not None and len(iv):
            covered = clip(iv, lo, hi)
            if (covered[:, 1] - covered[:, 0]).sum() * 2 > hi - lo:
                return name
    return "idle"


def reduce(devices, host, modules: Dict[str, List[str]]) -> Summary:
    win = [e for e in host if e.name == WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = win[0].start_ns, win[0].end_ns
    spans = {
        name: union(np.array([[e.start_ns, e.end_ns] for e in host if e.name == name],
                             np.int64).reshape(-1, 2))
        for name in GAP_SPANS
    }
    patterns = {g: [re.compile(p) for p in ps] for g, ps in modules.items()}
    busy, gaps = [], []
    module_s = {g: [] for g in modules}
    module_runs = {g: [] for g in modules}
    op_s: Dict[str, float] = {}
    for dev in sorted(devices):
        lines = devices[dev]
        ops = [e for e in lines.get(OPS_LINE, []) if e.end_ns > lo and e.start_ns < hi]
        iv = union(clip(np.array([[e.start_ns, e.end_ns] for e in ops], np.int64)
                        .reshape(-1, 2), lo, hi))
        busy.append(float((iv[:, 1] - iv[:, 0]).sum()) / 1e9)
        for e in ops:
            d = (min(e.end_ns, hi) - max(e.start_ns, lo)) / 1e9
            op_s[e.name] = op_s.get(e.name, 0.0) + d
        edges = np.concatenate([[lo], iv.ravel(), [hi]]).reshape(-1, 2)
        for g_lo, g_hi in edges:
            if g_hi > g_lo:
                gaps.append((_name_gap(int(g_lo), int(g_hi), spans), (g_hi - g_lo) / 1e9))
        mods = [e for e in lines.get(MODULES_LINE, []) if e.start_ns >= lo and e.end_ns <= hi]
        for g, ps in patterns.items():
            sel = [e for e in mods if any(p.search(e.name) for p in ps)]
            module_s[g].append(sum(e.end_ns - e.start_ns for e in sel) / 1e9)
            module_runs[g].append(len(sel))
    top = sorted(op_s.items(), key=lambda kv: -kv[1])
    gaps.sort(key=lambda g: -g[1])
    return Summary((hi - lo) / 1e9, busy, module_s, module_runs, top, gaps)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: str, modules: Dict[str, List[str]]) -> Summary:
    devices, host = planes(find_xplane(trace_dir))
    return reduce(devices, host, modules)
