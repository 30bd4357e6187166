"""Host milliseconds in Cluster.serve outside the backend per served batch, closed loop."""
from chipbench import readers


def read(run):
    return readers.broker_ms(run, "closed")
