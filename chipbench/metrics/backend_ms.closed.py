"""Host milliseconds in the miss backend per served batch, closed loop."""
from chipbench import readers


def read(run):
    return readers.backend_ms(run, "closed")
