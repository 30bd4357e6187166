#!/usr/bin/env python3
"""Readings of the check's two ends, on the chip, for the limits.

  python3 chipbench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed, in one process: the cell's set-up and a window, then the
check twice: once of what the program served (the lower reading), once
with the control in the program's place (the upper reading).  The
control is the plain reference with one guarantee broken: hits do not
refresh recency, so its sets are FIFO, not LRU, the shortcut that would
save the commit of every hit.  Prints one JSON line per seed.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    from chipbench import harness

    harness.configure(ROOT)

    cell = harness.load_cell(ROOT, args.workload)
    harness.check_devices(cell.chips, require_tpu=True)
    for seed in args.seeds:
        t = time.perf_counter()
        setup = harness.set_up(cell, seed)
        res, _ = harness.measure(setup, seed, args.seconds, False, "")
        setup.cluster.close()
        setup.cluster = None
        gc.collect()
        program, _ = harness.check(setup, res)
        control, _ = harness.check(setup, res, control=True)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "requests": res.requests,
            "program": {k: v["value"] for k, v in program.items()},
            "control": {k: v["value"] for k, v in control.items()},
            "seconds": time.perf_counter() - t,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
