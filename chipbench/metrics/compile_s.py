"""Seconds the process spent in backend compiles (JAX's monitoring events)."""


def read(run):
    return run["compile_s"]
