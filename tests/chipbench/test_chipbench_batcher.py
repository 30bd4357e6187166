"""The wall-clock load loops: latency from the schedule, lateness of the
generator, batches closed by size or deadline, no wrap-around."""
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import batcher  # noqa: E402


class Server:
    """Answers each query with its id; stalls once for ``stall_s``."""

    def __init__(self, stall_at=None, stall_s=0.0, service_s=0.0):
        self.stall_at, self.stall_s, self.service_s = stall_at, stall_s, service_s
        self.calls = 0
        self.stall = None

    def __call__(self, q):
        if self.calls == self.stall_at:
            a = time.perf_counter()
            time.sleep(self.stall_s)
            self.stall = (a, time.perf_counter())
        self.calls += 1
        time.sleep(self.service_s)
        return np.repeat(q[:, None], 2, 1).astype(np.int32), q % 2 == 0


def test_stalled_server_raises_latency_of_requests_due_during_the_stall():
    n = 400
    t = np.arange(n) / 1000.0  # 1000 requests per second
    keys = np.arange(10_000, dtype=np.int64)
    server = Server(stall_at=20, stall_s=0.15)
    res = batcher.open_loop(server, keys, t, max_batch=64, deadline_s=0.002,
                            value_dim=2)
    assert res.requests == n and sum(res.batch_sizes) == n
    assert np.array_equal(res.values[:, 0], keys[:n])
    assert np.array_equal(res.hits, keys[:n] % 2 == 0)
    a, b = (x - res.started for x in server.stall)
    during = (t >= a) & (t < b)
    assert during.sum() > 50
    # every request due during the stall waited at least until it ended
    assert np.all(res.latency_s[during] >= (b - t[during]) - 1e-6)
    assert res.latency_s[during].max() >= 0.1
    # and the tail of all requests shows it
    assert np.percentile(res.latency_s, 99) >= 0.1
    assert max(res.batch_sizes) == 64  # the backlog closed full batches
    assert len(res.late_s) == n and np.all(res.late_s >= 0)
    # the loop's lateness is not the server's stall
    assert np.percentile(res.late_s, 99) < 0.05


def test_lateness_of_a_slow_load_loop_is_reported():
    n = 300
    t = np.arange(n) / 2000.0

    def slow_sleep(s):
        time.sleep(s + 0.02)  # the loop oversleeps every wait

    res = batcher.open_loop(Server(), np.arange(n, dtype=np.int64), t,
                            max_batch=4096, deadline_s=0.001, value_dim=2,
                            sleep=slow_sleep)
    assert res.requests == n
    assert np.percentile(res.late_s, 99) >= 0.015
    # latency counts the loop's lateness: it runs from the schedule
    assert np.all(res.latency_s >= res.late_s)
    on_time = batcher.open_loop(Server(), np.arange(n, dtype=np.int64), t,
                                max_batch=4096, deadline_s=0.001, value_dim=2)
    assert np.median(on_time.late_s) < np.median(res.late_s)


class VirtualClock:
    """A clock that moves only when the loop sleeps: the batching policy
    without the host's scheduling noise."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now

    def sleep(self, s):
        self.now += s


def test_light_load_closes_batches_on_the_deadline():
    n = 40
    t = np.arange(n) / 200.0  # one request every 5 ms
    clock = VirtualClock()
    res = batcher.open_loop(Server(), np.arange(n, dtype=np.int64), t,
                            max_batch=4096, deadline_s=0.003, value_dim=2,
                            clock=clock, sleep=clock.sleep)
    assert res.batch_sizes == [1] * n
    # a lone request waits out its deadline before its batch closes
    assert np.allclose(res.latency_s, 0.003)
    assert np.allclose(res.late_s, 0.0)


def test_closed_loop_serves_full_batches_and_never_wraps():
    keys = np.arange(64 * 100, dtype=np.int64)
    res = batcher.closed_loop(Server(service_s=0.002), keys, 64, 0.05, value_dim=2)
    assert set(res.batch_sizes) == {64}
    assert np.array_equal(res.values[:, 0], keys[: res.requests])
    assert res.window_s >= 0.05 and 0 < res.requests < len(keys)
    with pytest.raises(batcher.StreamExhausted):
        batcher.closed_loop(Server(), keys[:640], 64, 5.0, value_dim=2)


def test_open_loop_refuses_a_stream_shorter_than_the_schedule():
    with pytest.raises(batcher.StreamExhausted):
        batcher.open_loop(Server(), np.arange(10, dtype=np.int64),
                          np.arange(20) / 1e3, 8, 0.001, 2)
