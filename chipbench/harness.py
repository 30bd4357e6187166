"""One run of one cell: set-up, warm-up, the measured window, the check.

Everything about a cell is data found by name: the cell in
``BENCHMARK.json`` names a configuration (``configs/<name>.json``) and a
traffic mix (``traffic/<name>.json``); each per-layer metric is a reader
in ``metrics/<name>.py``.  From the program the run takes the system
under test (the served cluster, built by the serve CLI's build
functions), its counters and its compiled programs' names.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Optional

import numpy as np

from . import arrivals, batcher, reference, streams, trace

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
#: the program's compile events (seconds per backend compile)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CellError(RuntimeError):
    """The run cannot produce a result (no chip, too few chips, a chip
    missing from the peaks, a cell that does not exist)."""


def configure(root: str) -> None:
    """Process settings every entry point makes before JAX starts its
    backend: the compile cache at a fixed path inside the checkout,
    caching every program, and no TPU runtime logs outside it."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    per_layer: list  # BENCHMARK.json entries that list this cell
    end_to_end: list


def load_cell(root: str, workload: str) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    traffic = _load_json(os.path.join(root, "chipbench", "traffic", w["traffic"] + ".json"))

    def mine(m):
        return workload in m.get("workloads", [workload])

    return Cell(
        workload, config, traffic, int(w["chips"]),
        [m for m in bench["per_layer"] if mine(m)],
        [m for m in bench["end_to_end"] if mine(m)],
    )


def check_devices(chips: int, require_tpu: bool):
    """The devices the cell runs on, and the chip's peaks; refuses a
    host with no TPU, too few chips, or a chip missing from the peaks."""
    import jax

    devices = jax.devices()
    kind = devices[0].device_kind
    peaks = _load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if require_tpu:
        if devices[0].platform != "tpu":
            raise CellError(f"JAX found no TPU (platform {devices[0].platform!r})")
        if len(devices) < chips:
            raise CellError(f"the cell needs {chips} chips, JAX found {len(devices)}")
        if kind not in peaks:
            raise CellError(f"device_kind {kind!r} is not in peaks.json")
    return devices[:chips], peaks.get(kind)


class RecordingBackend:
    """The miss backend with a host clock and a span around every call,
    keeping each call's queries and answers for the check.  Shards call
    it from their own threads, so calls can overlap."""

    def __init__(self, fn):
        import jax

        self.fn = fn
        self._span = jax.profiler.TraceAnnotation
        self.intervals: list = []  # (start, end) host seconds per call
        self.keys: list = []
        self.rows: list = []

    def __call__(self, q):
        t = time.perf_counter()
        with self._span("bench.backend"):
            out = self.fn(q)
        self.intervals.append((t, time.perf_counter()))
        self.keys.append(np.array(q, np.int64))
        self.rows.append(out)
        return out

    @property
    def calls(self) -> int:
        return len(self.intervals)

    def busy_since(self, first_call: int) -> float:
        """Seconds in which at least one call since ``first_call`` ran."""
        iv = sorted(self.intervals[first_call:])
        total, end = 0.0, -np.inf
        for a, b in iv:
            if b > end:
                total += b - max(a, end)
                end = b
        return total

    def recorded(self):
        return np.concatenate(self.keys), np.concatenate(self.rows)


def _topic_pipeline(log_, **params):
    """The program's topic discovery (LDA over clicked documents)."""
    from repro.topics import run_pipeline

    pipe = run_pipeline(log_, **params)
    return pipe.assignment.key_topic, pipe.log.n_train


def _build_cluster(config: dict, traffic: dict, stream, backend):
    """The served cluster, as the serve CLI builds it, on the device
    engine."""
    from repro.core.fast import VecLog, VecStats
    from repro.launch import serve

    args = serve.build_parser().parse_args([
        "--strategy", config["strategy"], "--f-s", str(config["f_s"]),
        "--f-t", str(config["f_t"]), "--f-ts", str(config["f_ts"]),
        "--entries", str(config["entries"]),
        "--batch", str(traffic["max_batch"]),
        "--value-dim", str(config["value_dim"]),
        "--shards", str(config["shards"]), "--routing", config["routing"],
    ])
    spec = dataclasses.replace(
        serve.spec_from_args(args), engine="device", ways=config["ways"]
    )
    log_ = VecLog(keys=stream.keys, n_train=stream.n_train, key_topic=stream.key_topic)
    s = serve.Stream(stream.synth, log_, VecStats.from_log(log_), stream.key_topic)
    return serve.build_cluster(spec, s, backend)


class _Compiles:
    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.count += 1

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on)


def _load_reader(name: str):
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _percentile(x: np.ndarray, q: float) -> float:
    return float(np.percentile(x, q)) if len(x) else float("nan")


@dataclasses.dataclass
class Setup:
    """A cell built and warmed up, ready for its window."""

    cell: Cell
    stream: streams.Stream
    backend: RecordingBackend
    cluster: object
    warm_sizes: list  # the warm-up prefix's batch sizes
    warm: int  # requests in the warm-up prefix

    @property
    def window_keys(self) -> np.ndarray:
        return self.stream.served[self.warm :]


def set_up(cell: Cell, seed: int) -> Setup:
    """Stream, backend and cluster, then a warm-up prefix of full batches
    until the set layers have taken ``n_sets * W`` inserts; by its end
    every shape the window can present is compiled."""
    from repro.launch import serve

    cfg, traffic = cell.config, cell.traffic
    t = time.perf_counter()
    stream = streams.make(traffic, seed, topic_pipeline=_topic_pipeline)
    served = stream.served
    log(f"stream: {len(stream.keys)} requests, {len(served)} after training, "
        f"made in {time.perf_counter() - t:.3f} s")
    batch = int(traffic["max_batch"])
    backend = RecordingBackend(serve.build_backend(
        cfg["backend_arch"], cfg["value_dim"], chunk=batch))
    t = time.perf_counter()
    cluster = _build_cluster(cfg, traffic, stream, backend)
    n_sets = sum(b.cache.n_sets for b in cluster.brokers)
    log(f"cluster: {cfg['shards']} shard(s), {n_sets} sets, built in "
        f"{time.perf_counter() - t:.3f} s")

    if traffic["loop"] != "closed" or cfg["shards"] > 1:
        # batches (or shard slices) of any size up to the largest can
        # occur: the program's own warm-up compiles its whole ladder.  A
        # closed loop on one shard presents one shape, which the warm-up
        # prefix's first batch compiles.
        t = time.perf_counter()
        shapes = cluster.warmup([batch])
        log(f"compiled {shapes} in {time.perf_counter() - t:.3f} s")

    sizes, pos = [], 0
    target = n_sets * int(cfg["ways"])
    t = time.perf_counter()
    while cluster.stats.admitted < target:
        if pos + batch > len(served):
            raise batcher.StreamExhausted("the stream ran out during warm-up")
        cluster.serve(served[pos : pos + batch])
        sizes.append(batch)
        pos += batch
    log(f"warm-up: {len(sizes)} batches, {cluster.stats.admitted} inserts "
        f"(target {target}), {time.perf_counter() - t:.3f} s")
    return Setup(cell, stream, backend, cluster, sizes, pos)


def arrival_times(traffic: dict, seed: int, seconds: float) -> np.ndarray:
    """The open loop's schedule: every arrival in the first ``seconds``."""
    a = dict(traffic["arrivals"])
    process, rate = a.pop("process"), float(a.pop("rate"))
    times = arrivals.PROCESSES[process]([seed, 1], rate, int(rate * seconds * 1.5) + 1000, **a)
    if times[-1] < seconds:
        raise CellError("the arrival schedule ends inside the window")
    return times[times < seconds]


def measure(setup: Setup, seed: int, seconds: float, traced: bool, trace_dir: str):
    """The window; returns the loop's result and the host seconds the
    backend took in each batch."""
    import jax

    traffic = setup.cell.traffic
    batch = int(traffic["max_batch"])
    v = int(setup.cell.config["value_dim"])
    backend, serve_fn = setup.backend, setup.cluster.serve
    backend_s: list = []

    def timed_serve(q):
        first = backend.calls
        out = serve_fn(q)
        backend_s.append(backend.busy_since(first))
        return out

    times = None if traffic["loop"] == "closed" else arrival_times(traffic, seed, seconds)
    jax.block_until_ready([b.state for b in setup.cluster.brokers])
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            if times is None:
                res = batcher.closed_loop(timed_serve, setup.window_keys, batch, seconds, v)
            else:
                res = batcher.open_loop(timed_serve, setup.window_keys, times, batch,
                                        float(traffic["deadline_ms"]) / 1e3, v)
    finally:
        if traced:
            jax.profiler.stop_trace()
    return res, np.array(backend_s)


def check(setup: Setup, res, control: bool = False):
    """Compare every answer of the window with the reference: the hit
    mask by a replay of every served batch from empty, the values with
    the backend's recorded answers.  With ``control`` the reference's
    FIFO variant is compared in the program's place.  Returns each
    number compared with its limit, and the count of requests that
    failed either."""
    cfg, stream = setup.cell.config, setup.stream
    t = time.perf_counter()
    sizes = np.array(setup.warm_sizes + res.batch_sizes)
    keys = stream.served[: setup.warm + res.requests]
    layout = reference.build_layout(
        stream.keys[: stream.n_train], stream.key_topic, cfg["entries"],
        cfg["f_s"], cfg["f_t"], cfg["f_ts"], cfg["ways"], cfg["shards"])
    want_hits = reference.replay(layout, keys, stream.key_topic, sizes)[setup.warm :]
    rec_k, rec_v = setup.backend.recorded()
    want_vals, found = reference.expected_values(keys[setup.warm :], rec_k, rec_v)
    hits, values = res.hits, res.values
    if control:
        hits = reference.replay(layout, keys, stream.key_topic, sizes,
                                refresh=False)[setup.warm :]
        values = want_vals
    hit_bad = want_hits != hits
    val_bad = ~found | np.any(want_vals != values, axis=1)
    log(f"reference: {layout.n_sets} sets, {len(layout.static_hashes)} static "
        f"keys, {len(sizes)} batches replayed in {time.perf_counter() - t:.3f} s; "
        f"its hit rate in the window {want_hits.mean():.6f}")
    checks = {
        "hit_mismatches": {"value": int(hit_bad.sum()), "limit": 0},
        "value_mismatches": {"value": int(val_bad.sum()), "limit": 0},
    }
    return checks, int((hit_bad | val_bad).sum())


def run_cell(root: str, workload: str, seed: int, seconds: float, traced: bool,
             started: float, require_tpu: bool = True,
             cell: Optional[Cell] = None) -> dict:
    """Run ``workload`` once; returns the result line's object.

    ``started`` is the process's start on ``time.perf_counter``'s clock.
    ``cell`` replaces the cell found by name (tests run small ones).
    """
    cell = cell or load_cell(root, workload)
    devices, peak = check_devices(cell.chips, require_tpu)
    compiles = _Compiles()
    try:
        setup = set_up(cell, seed)
        setup_s = time.perf_counter() - started
        admitted0 = setup.cluster.stats.admitted
        calls0 = setup.backend.calls
        c0 = (compiles.count, compiles.seconds)
        trace_dir = os.path.join(root, ".chipbench_trace")
        res, backend_s = measure(setup, seed, seconds, traced, trace_dir)
        log(f"window: {res.requests} requests in {len(res.batch_sizes)} batches, "
            f"{res.window_s:.3f} s; compiles in the window: {compiles.count - c0[0]} "
            f"({compiles.seconds - c0[1]:.3f} s)")
        log(f"stream headroom: {len(setup.window_keys)} measured requests, "
            f"{len(setup.window_keys) / max(res.requests, 1):.2f}x what the window "
            f"consumed")
        compile_s = compiles.seconds
    finally:
        compiles.close()
    cluster = setup.cluster
    inserts = cluster.stats.admitted - admitted0
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    s = cluster.stats
    log(f"hit rate {s.hit_rate:.6f}: static {s.static_hits}, set layers "
        f"{s.topic_hits}, of {s.requests}; backend calls "
        f"{setup.backend.calls - calls0} in the window")
    cluster.close()
    setup.cluster = cluster = None
    gc.collect()

    summary = None
    if traced:
        t = time.perf_counter()
        summary = trace.reduce_dir(trace_dir, _load_json(os.path.join(BENCH_DIR, "modules.json")))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t:.3f} s")
    checks, failed = check(setup, res)

    run = dict(  # what the per-layer readers read
        loop=cell.traffic["loop"], config=cell.config, peak=peak,
        requests=res.requests, batches=len(res.batch_sizes),
        serve_s=np.array(res.serve_s), backend_s=backend_s, late_s=res.late_s,
        inserts=inserts, compile_s=compile_s, trace=summary,
    )
    metrics = {}
    if traced:
        for m in cell.per_layer:
            val = _load_reader(m["name"])(run)
            if val is not None:
                metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    else:
        e2e = {
            "throughput_rps": lambda: res.requests / res.window_s,
            "latency_p50_ms": lambda: _percentile(res.latency_s, 50) * 1e3,
            "latency_p99_ms": lambda: _percentile(res.latency_s, 99) * 1e3,
            "setup_s": lambda: setup_s,
        }
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]()), "unit": m["unit"]}
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": int(memory_peak),
    }
    out = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": int(res.requests),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    out["checks"] = checks
    return out
