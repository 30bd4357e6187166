#!/usr/bin/env python3
"""Name the device's idle time by the served program's own phases.

  python3 chipbench/phases.py --workload <cell> --seed <n> --seconds <s>

The program opens profiler spans for the phases of every batch
(``cluster.serve``, ``cluster.route``, ``broker.serve``,
``broker.prepare``, ``broker.dispatch``, ``broker.readback``,
``broker.miss``, ``broker.fill_plan``, ``broker.flush``) on the host
plane of the same trace, and on the same clock, as the device's
operations; ``BrokerStats`` counts the transfers behind them.  This
script runs one cell's window traced, as ``run.py --trace 1`` does, and
prints one JSON line: per served batch, the device-idle milliseconds
attributed to each span, the window's counters (readbacks, uploads,
replay rounds, jit traces), the longest idle gaps with the span and
batch that hold each, and the window's garbage collections (a pause of
the host shows as an idle gap of the same length).

Attribution (``idle_by_span``): each device's idle time inside
``bench.window`` is split exactly at span boundaries, and each piece
goes to the innermost span that covers it -- the program's spans before
the benchmark's, the latest-started first (the deepest, where spans
nest on one thread) -- or to ``idle`` where none does.  The pieces sum
to the device's idle time; the result is the mean over devices.

The benchmark's result line does not carry these numbers yet: that
needs the harness to keep the program's spans and the counters' deltas.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from typing import Dict, List, Sequence

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)

from chipbench import trace  # noqa: E402

#: name prefixes of the program's spans
PROGRAM = ("cluster.", "broker.")
#: the device idle each phase metric reads, by span
PHASES = {
    "prepare": ("cluster.route", "broker.prepare", "broker.dispatch"),
    "readback": ("broker.readback", "broker.fill_plan"),
    "miss": ("broker.miss",),
}
#: BrokerStats counters read per batch
COUNTERS = ("readbacks", "uploads", "replay_rounds")


@dataclasses.dataclass
class Span:
    name: str
    start_ns: int
    end_ns: int
    meta: dict


def host_spans(pd) -> List[Span]:
    """The program's and the benchmark's spans on the host plane."""
    out = []
    for pl in pd.planes:
        if pl.name != trace.HOST_PLANE:
            continue
        for ln in pl.lines:
            for e in ln.events:
                if e.name.startswith(PROGRAM + ("bench.",)):
                    start = int(e.start_ns)
                    out.append(Span(e.name, start, start + int(e.duration_ns),
                                    {k: v for k, v in e.stats}))
    return out


def _busy_before(iv: np.ndarray, points: np.ndarray) -> np.ndarray:
    """Busy nanoseconds before each point, for disjoint sorted ``iv``."""
    if not len(iv):
        return np.zeros(len(points), np.int64)
    lengths = iv[:, 1] - iv[:, 0]
    done = np.concatenate([[0], np.cumsum(lengths)])
    k = np.searchsorted(iv[:, 0], points, side="right")  # intervals begun
    j = np.maximum(k - 1, 0)
    partial = np.clip(points - iv[j, 0], 0, lengths[j])
    return np.where(k > 0, done[j] + partial, 0)


def _owners(spans: Sequence[Span], lo: int, hi: int):
    """Elementary pieces of [lo, hi) between span boundaries, and the
    name of the innermost span covering each (``idle`` where none)."""
    cuts = {lo, hi}
    for s in spans:
        cuts.update(min(max(t, lo), hi) for t in (s.start_ns, s.end_ns))
    points = np.array(sorted(cuts), np.int64)
    starts: Dict[int, list] = {}
    ends: Dict[int, list] = {}
    for k, s in enumerate(spans):
        a, b = max(s.start_ns, lo), min(s.end_ns, hi)
        if b > a:
            starts.setdefault(a, []).append(k)
            ends.setdefault(b, []).append(k)
    active: set = set()
    names = []
    for p in points[:-1]:
        active.difference_update(ends.get(int(p), ()))
        active.update(starts.get(int(p), ()))
        if active:
            k = max(active, key=lambda i: (spans[i].name.startswith(PROGRAM),
                                           spans[i].start_ns, -spans[i].end_ns))
            names.append(spans[k].name)
        else:
            names.append("idle")
    return points, names


def busy_intervals(devices, lo: int, hi: int) -> Dict[int, np.ndarray]:
    """Per device, the union of its operations' intervals in [lo, hi)."""
    out = {}
    for dev in sorted(devices):
        ops = devices[dev].get(trace.OPS_LINE, [])
        iv = np.array([[e.start_ns, e.end_ns] for e in ops], np.int64).reshape(-1, 2)
        out[dev] = trace.union(trace.clip(iv, lo, hi))
    return out


def idle_by_span(busy: Dict[int, np.ndarray], spans: Sequence[Span], lo: int,
                 hi: int) -> Dict[str, float]:
    """Seconds of device idle attributed to each span name (innermost
    first, split exactly at span boundaries), mean over devices."""
    points, names = _owners([s for s in spans if s.name != trace.WINDOW_SPAN], lo, hi)
    total: Dict[str, float] = {}
    for iv in busy.values():
        b = _busy_before(iv, points)
        idle = np.diff(points) - np.diff(b)
        for name, ns in zip(names, idle):
            total[name] = total.get(name, 0.0) + float(ns) / 1e9
    n = max(len(busy), 1)
    return {k: v / n for k, v in total.items() if v > 0}


def longest_gaps(busy: Dict[int, np.ndarray], spans: Sequence[Span], lo: int, hi: int,
                 top: int = 10) -> list:
    """The longest idle gaps of all devices, each with the span that holds
    most of its idle time and the batch (``seq``) of the innermost
    ``broker.serve`` or ``cluster.serve`` around its middle."""
    gaps = []
    for dev, iv in busy.items():
        edges = np.concatenate([[lo], iv.ravel(), [hi]]).reshape(-1, 2)
        gaps.extend((int(g_hi - g_lo), dev, int(g_lo), int(g_hi))
                    for g_lo, g_hi in edges if g_hi > g_lo)
    gaps = sorted(gaps, reverse=True)[:top]
    points, names = _owners([s for s in spans if s.name != trace.WINDOW_SPAN], lo, hi)
    serves = [s for s in spans if s.name in ("broker.serve", "cluster.serve")]
    out = []
    for ns, dev, g_lo, g_hi in gaps:
        share: Dict[str, int] = {}
        for name, piece in zip(names, np.diff(np.clip(points, g_lo, g_hi))):
            share[name] = share.get(name, 0) + int(piece)
        mid = (g_lo + g_hi) // 2
        around = [s for s in serves if s.start_ns <= mid < s.end_ns]
        inner = max(around, key=lambda s: s.start_ns) if around else None
        out.append({"device": dev, "ms": ns / 1e6, "span": max(share, key=share.get),
                    "seq": inner.meta.get("seq") if inner else None})
    return out


def reduce(pd, batches: int) -> dict:
    """Per batch: device-idle ms by span and by phase, from one trace."""
    devices, host = trace.planes_of(pd)
    win = [e for e in host if e.name == trace.WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = win[0].start_ns, win[0].end_ns
    spans = host_spans(pd)
    busy = busy_intervals(devices, lo, hi)
    by_span = idle_by_span(busy, spans, lo, hi)
    idle_s = float(np.mean([(hi - lo - (iv[:, 1] - iv[:, 0]).sum()) / 1e9
                            for iv in busy.values()])) if busy else 0.0
    per = 1e3 / max(batches, 1)
    return {
        "idle_ms": idle_s * per,
        "idle_ms_by_span": {k: v * per for k, v in sorted(by_span.items())},
        "idle_ms_by_phase": {p: sum(by_span.get(n, 0.0) for n in names) * per
                             for p, names in PHASES.items()},
        "gaps": longest_gaps(busy, spans, lo, hi),
        "spans_per_batch": sum(s.name.startswith(PROGRAM) and lo <= s.start_ns < hi
                               for s in spans) / max(batches, 1),
    }


def counters(before, after, traces_before: dict, traces_after: dict, batches: int) -> dict:
    """The window's counter deltas per batch, and its jit traces."""
    out = {c: (getattr(after, c) - getattr(before, c)) / max(batches, 1) for c in COUNTERS}
    out["window_traces"] = sum(traces_after.values()) - sum(traces_before.values())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from chipbench import harness
    from jax._src.profiler import ProfileData

    harness.configure(ROOT)
    try:
        cell = harness.load_cell(ROOT, args.workload)
        devices, _ = harness.check_devices(cell.chips, require_tpu=True)
    except harness.CellError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    setup = harness.set_up(cell, args.seed)
    cluster = setup.cluster
    s0, t0 = cluster.stats, dict(cluster.trace_counts)
    trace_dir = os.path.join(ROOT, ".chipbench_trace")
    pauses = []  # [generation, ms] of each collection in the window
    began = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            began[0] = time.perf_counter()
        else:
            pauses.append([info["generation"], 1e3 * (time.perf_counter() - began[0])])

    gc.callbacks.append(on_gc)
    try:
        res, _ = harness.measure(setup, args.seed, args.seconds, True, trace_dir)
    finally:
        gc.callbacks.remove(on_gc)
    batches = len(res.batch_sizes)
    out = {"workload": args.workload, "seed": args.seed,
           "device": devices[0].device_kind, "batches": batches,
           "traced_rps": res.requests / res.window_s}
    out.update(counters(s0, cluster.stats, t0, cluster.trace_counts, batches))
    out["gc_pauses"] = sorted(pauses, key=lambda p: -p[1])[:5]
    out["gc_count"] = len(pauses)
    cluster.close()
    t = time.perf_counter()
    out.update(reduce(ProfileData.from_file(trace.find_xplane(trace_dir)), batches))
    shutil.rmtree(trace_dir, ignore_errors=True)
    harness.log(f"trace reduced in {time.perf_counter() - t:.3f} s")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
