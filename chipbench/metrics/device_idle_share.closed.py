"""Share of the traced window (%) in which no operation ran on the device, mean over the cell's devices; closed loop."""
from chipbench import readers


def read(run):
    return readers.idle_share(run, "closed")
