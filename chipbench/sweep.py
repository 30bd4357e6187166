#!/usr/bin/env python3
"""Find the knee of an open-loop cell: one set-up, then a window at each
offered rate, one after another on the stream.

  python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates <r> [<r> ...]

For each rate it prints the achieved rate, the latency quantiles, how
long the queue took to drain after the last arrival, and the p99 of the
window's first and second half: a backlog that grows shows as a
second half far above the first.  The cell's traffic file then fixes its
rate at four fifths of the highest rate that held.
"""
import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    import numpy as np

    from chipbench import harness

    harness.configure(ROOT)

    cell = harness.load_cell(ROOT, args.workload)
    harness.check_devices(cell.chips, require_tpu=True)
    setup = harness.set_up(cell, args.seed)
    for k, rate in enumerate(args.rates):
        traffic = dict(cell.traffic, arrivals=dict(cell.traffic["arrivals"], rate=rate))
        setup.cell = dataclasses.replace(cell, traffic=traffic)
        res, backend_s = harness.measure(setup, args.seed + k, args.seconds, False, "")
        lat = res.latency_s * 1e3
        half = len(lat) // 2
        out = {
            "rate": rate, "requests": res.requests, "batches": len(res.batch_sizes),
            "achieved_rps": res.requests / res.window_s,
            "p50_ms": float(np.percentile(lat, 50)), "p99_ms": float(np.percentile(lat, 99)),
            "p99_first_half_ms": float(np.percentile(lat[:half], 99)),
            "p99_second_half_ms": float(np.percentile(lat[half:], 99)),
            "drain_s": res.window_s - args.seconds,
            "mean_batch": float(np.mean(res.batch_sizes)),
            "backend_ms": 1e3 * float(np.mean(backend_s)),
        }
        print(json.dumps(out), flush=True)
        setup.warm_sizes += res.batch_sizes
        setup.warm += res.requests
    setup.cluster.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
