#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip this machine holds.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration and its
traffic mix are found by name through ``BENCHMARK.json``.  The run makes
its stream from ``--seed``, builds the served cluster, warms it up,
measures for ``--seconds``, then checks every answer of the window
against the plain reference (``reference.py``).  With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.

The last line of standard output is the result, one JSON object; the
numbers the check compared, each with its limit, are the last lines of
standard error.  The run exits nonzero and prints no result when JAX
finds no TPU, fewer chips than the cell needs, or a chip missing from
``peaks.json``, and when the stream runs out inside the window.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _process_age() -> float:
    """Seconds since this process started, from the kernel's record
    (falls back to the first line of this file)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - STARTED


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    started = time.perf_counter() - _process_age()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    try:
        import repro  # noqa: F401  the program under test
    except ImportError as e:
        print(f"chipbench: the program is not in this checkout ({e})", file=sys.stderr)
        return 1
    from chipbench import harness

    harness.configure(ROOT)
    try:
        out = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                               bool(args.trace), started)
    except (harness.CellError, harness.batcher.StreamExhausted) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
