"""Device time of the cache's compiled step per served batch (ms), open loop."""
from chipbench import readers


def read(run):
    return readers.step_ms(run, "open")
