"""Bytes the STD cache's served step must move, counted from widths.

The least traffic to HBM that the semantics of one served batch need,
whatever implements them, from the configuration's widths alone
(``ways`` W and ``value_dim`` V, 4-byte words):

* the request's set: its 4W words (key high, key low, recency, epoch)
  read, and written back;
* one static-layer key (two words) compared;
* one value row (V words) read, and one written per insert;
* the request's inputs (64-bit hash, partition, admit flag) and outputs
  (value row, hit flag).

A roofline share is these bytes over the chip's HBM bandwidth, divided
by the measured device time of the step.
"""
from __future__ import annotations

WORD = 4


def widths(cfg: dict) -> tuple:
    """The widths the count reads: (ways, value_dim)."""
    return int(cfg["ways"]), int(cfg["value_dim"])


def step_bytes(cfg: dict, requests: int, inserts: int) -> int:
    """Bytes the semantics must move to serve ``requests`` requests of
    which ``inserts`` were inserted into a set."""
    w, v = widths(cfg)
    set_words = 4 * w * WORD
    per_request = (
        2 * set_words  # the set's words, read and written
        + 2 * WORD  # one static key
        + v * WORD  # the value row read
        + (2 * WORD + WORD + 1)  # inputs: hash, partition, admit flag
        + (v * WORD + 1)  # outputs: value row, hit flag
    )
    return requests * per_request + inserts * v * WORD


def roofline_share(cfg: dict, requests: int, inserts: int, device_s: float,
                   hbm_bytes_per_s: float) -> float:
    """Percent of the HBM roofline the step reached in ``device_s``."""
    return 100.0 * step_bytes(cfg, requests, inserts) / hbm_bytes_per_s / device_s
