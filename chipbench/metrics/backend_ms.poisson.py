"""Host milliseconds in the miss backend per served batch, open loop."""
from chipbench import readers


def read(run):
    return readers.backend_ms(run, "open")
