"""The load loops that drive the served path on the wall clock.

* ``closed_loop`` serves consecutive full batches back to back, as
  callers that each wait for their answer would, until the window has
  run for its seconds.
* ``open_loop`` takes requests at their scheduled times, as independent
  searchers send them.  The batcher closes a batch when ``max_batch``
  requests are pending or when the oldest pending one has waited
  ``deadline_s`` past its scheduled arrival, then calls the server,
  which blocks.  A request's latency runs from its scheduled arrival to
  its answer, so a stalled server raises the latency of every request
  due during the stall.  How late the loop itself took each request is
  reported beside it.  The loop runs in the serving thread: a generator
  thread of its own would contend for the interpreter lock with the
  server's host code and slow it.

Neither loop wraps around its stream: a stream that runs out raises
``StreamExhausted``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List

import numpy as np

try:  # host spans in the profiler's trace; absent outside JAX
    from jax.profiler import TraceAnnotation
except ImportError:  # pragma: no cover
    import contextlib

    def TraceAnnotation(name):  # noqa: N802
        return contextlib.nullcontext()


class StreamExhausted(RuntimeError):
    """The measured part of the stream ran out inside the window."""


@dataclasses.dataclass
class LoopResult:
    batch_sizes: List[int]
    #: host seconds each batch spent in the server call
    serve_s: List[float]
    #: window seconds: first request due (or sent) to last answer
    window_s: float
    #: per request, seconds from scheduled arrival to answer (open loop)
    latency_s: np.ndarray
    #: per request, seconds the load loop took it later than it could
    late_s: np.ndarray
    values: np.ndarray
    hits: np.ndarray
    #: the clock's reading when the window started
    started: float = 0.0

    @property
    def requests(self) -> int:
        return int(sum(self.batch_sizes))


def closed_loop(serve: Callable, keys: np.ndarray, batch: int, seconds: float,
                value_dim: int, clock=time.perf_counter) -> LoopResult:
    """Serve full batches of ``keys`` until ``seconds`` have passed."""
    cap = len(keys) // batch
    values = np.zeros((cap * batch, value_dim), np.int32)
    hits = np.zeros(cap * batch, bool)
    sizes, serve_s = [], []
    t0 = clock()
    k = 0
    while clock() - t0 < seconds:
        if k >= cap:
            raise StreamExhausted(
                f"closed loop used all {cap} batches of the stream inside "
                f"{seconds} s; lengthen the traffic's stream"
            )
        lo = k * batch
        t = clock()
        with TraceAnnotation("bench.serve"):
            v, h = serve(keys[lo : lo + batch])
        serve_s.append(clock() - t)
        values[lo : lo + batch], hits[lo : lo + batch] = v, h
        sizes.append(batch)
        k += 1
    window = clock() - t0
    n = k * batch
    return LoopResult(sizes, serve_s, window, np.zeros(0), np.zeros(0),
                      values[:n], hits[:n], t0)


def open_loop(serve: Callable, keys: np.ndarray, t_sched: np.ndarray,
              max_batch: int, deadline_s: float, value_dim: int,
              clock=time.perf_counter, sleep=time.sleep) -> LoopResult:
    """Serve ``keys[i]`` arriving at ``t_sched[i]`` seconds after the
    start, batched by size and deadline; returns when all are answered.

    One thread: the schedule is the generator.  The batcher sleeps until
    the policy lets it close the next batch (the server free, and either
    ``max_batch`` requests due or the oldest one's deadline passed), then
    takes every request due by then, up to ``max_batch``.  A request's
    lateness is how long after it was both due and closable its batch
    was closed: the load loop's own delay (oversleeping, bookkeeping),
    never the server's."""
    n = len(t_sched)
    if len(keys) < n:
        raise StreamExhausted(
            f"open loop needs {n} requests, the stream holds {len(keys)}"
        )
    done_at = np.zeros(n)
    late = np.zeros(n)
    values = np.zeros((n, value_dim), np.int32)
    hits = np.zeros(n, bool)
    sizes, serve_s = [], []
    served, free_at = 0, 0.0
    t0 = clock()
    while served < n:
        full = served + max_batch - 1
        close_at = max(free_at, min(t_sched[full] if full < n else np.inf,
                                    t_sched[served] + deadline_s))
        now = clock() - t0
        if now < close_at:
            with TraceAnnotation("bench.batcher_wait"):
                sleep(close_at - now)
            now = clock() - t0
        hi = min(int(np.searchsorted(t_sched, now, side="right")), served + max_batch)
        lo = served
        late[lo:hi] = now - np.maximum(t_sched[lo:hi], close_at)
        t = clock()
        with TraceAnnotation("bench.serve"):
            v, h = serve(keys[lo:hi])
        end = clock()
        serve_s.append(end - t)
        free_at = done_at[lo:hi] = end - t0
        values[lo:hi], hits[lo:hi] = v, h
        sizes.append(hi - lo)
        served = hi
    return LoopResult(sizes, serve_s, float(done_at[-1]) if n else 0.0,
                      done_at - t_sched, late, values, hits, t0)
