"""99th percentile of how late (ms) the load generator sent its requests after their schedule."""
from chipbench import readers


def read(run):
    return readers.late_p99_ms(run, "open")
