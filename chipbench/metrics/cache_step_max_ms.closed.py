"""The busiest chip's device time in the cache's compiled step per served
batch (ms), closed loop: the largest per-chip total over the window,
divided by the batches. Beside the mean over chips that
cache_step_ms.closed gives, this is the step the gather waits on while
one chip is the straggler in every batch; where the straggler moves from
batch to batch it reads below the mean per-batch slowest step."""
from chipbench import readers


def read(run):
    t = readers._traced(run, "closed")
    if t is None or not run["batches"] or not sum(t.module_runs.get("cache_step", [])):
        return None
    return 1e3 * max(t.module_s["cache_step"]) / run["batches"]
