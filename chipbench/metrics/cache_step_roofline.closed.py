"""Share of the HBM roofline (%) the cache step reached: the bytes the STD semantics must move over the chip's bandwidth, over the step's device time; closed loop."""
from chipbench import readers


def read(run):
    return readers.step_roofline(run, "closed")
