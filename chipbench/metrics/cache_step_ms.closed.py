"""Device time of the cache's compiled step per served batch (ms), closed loop."""
from chipbench import readers


def read(run):
    return readers.step_ms(run, "closed")
