"""Front-end broker (paper Fig. 2): cache -> backend dispatch -> reply.

The broker owns the device-resident STD cache and a set of backend
executors (model shards).  Per batch:

1. hash + topic-route every query, and -- on shape-bucketed deployments
   -- pad the batch up to its bucket with the reserved never-resident
   pad key so the jitted device path sees O(#buckets) shapes instead of
   one trace per distinct batch length,
2. one fused serve device call (repro.kernels.cache_ops): hits are
   answered immediately and every cache write -- hit refreshes and
   admitted-miss inserts -- lands in the same call, in arrival order.
   On the default device path (``fused_one_call``) the previous batch's
   deferred value fill, the probe, the commit scatter and the probed
   value-row gather are **one** jitted entry point (one Pallas kernel
   under ``use_kernel``), so a served batch is exactly one device
   dispatch -- counted per call in ``Broker.dispatch_counts`` and pinned
   by the dispatch-count regression tests.  ``fused_one_call=False``
   restores the legacy pair of fused entry points (conformance-pinned),
3. misses are dispatched to a backend in micro-batches with **hedged
   requests** (a straggling micro-batch is re-dispatched to a backup
   executor; first result wins),
4. backend results are scattered into the slots the fused call reserved
   (deferred value fill).  On the device engine the fill is
   *double-buffered*: it rides inside the next batch's fused call
   (applied before that probe reads values), saving a dispatch per
   batch and letting XLA overlap the value scatter with the next
   bucket's key/stamp gather.  ``flush()`` applies a pending fill on
   demand; checkpoints and rebalances flush automatically.

``fused=False`` restores the PR-1 three-call path (probe, miss commit,
hit-refresh commit), now running on the vectorized batch commit with
the same bucket padding on its data-dependent miss/refresh sub-batches.

Every jitted entry point counts its traces in ``Broker.trace_counts``
(the python wrapper body only runs when jax traces), which is what the
compile-count regression tests pin, and compiles as a program named
``jit_wrapper.<entry>`` (``jit_wrapper.one_call``, ``jit_wrapper.fill``,
...), so a device trace tells the entries apart.

Tracing: ``serve`` opens ``jax.profiler.TraceAnnotation`` spans for its
phases -- ``broker.serve`` (metadata: shard, batch seq, b, padded bp)
around ``broker.prepare`` (hash, route, pad, freshness arrays,
admission), ``broker.dispatch`` (the pending fill plan, the uploads, the
jitted call, which returns once enqueued, and the request that every
host-bound output be copied to the host as soon as the step ends),
``broker.readback`` (the wait for the step and those copies),
``broker.miss`` (coalescing and the backend) and ``broker.fill_plan``
(the deferred fill's plan, from arrays already copied), plus
``broker.flush`` for a standalone fill.  They land in the same trace, on
the same clock, as the device's operations, and cost one check each when
no profiler session is active.  ``BrokerStats`` counts the transfers
behind them (``readbacks``, ``uploads``, ``early_copies``) and the
conflict replay's depth (``replay_rounds``) and width
(``replay_lane_rounds``).

Fault tolerance: `checkpoint` / `restore` snapshot the full cache state
atomically (repro.train.checkpoint); a broker can restart mid-stream and
continue with its hit rate intact -- exercised by tests.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from concurrent.futures import FIRST_COMPLETED, ThreadPoolExecutor, wait
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core.alloc import allocation_divergence
from ..core.spec import CacheSpec
from ..freshness import FreshnessRuntime, FreshnessSpec
from ..kernels.cache_ops import replay_lane_rounds
from ..train import checkpoint as ckpt_lib
from . import autotune
from .device_cache import (
    DYNAMIC,
    PAD_H64,
    DeviceCacheConfig,
    STDDeviceCache,
    pack_hashes,
    pad_batch,
    splitmix64,
    unpack_state,
)
from .rebalance import PopularityTracker, RebalanceSpec
from .spec import BucketSpec


@dataclasses.dataclass
class BrokerStats:
    requests: int = 0
    hits: int = 0
    static_hits: int = 0
    topic_hits: int = 0
    backend_calls: int = 0
    hedged_calls: int = 0
    admitted: int = 0
    #: duplicate in-batch misses answered from a single backend call
    coalesced: int = 0
    #: pad requests appended by shape bucketing (never counted in
    #: ``requests``; pad overhead = padded / (requests + padded))
    padded: int = 0
    #: non-empty batches served (the rebalance trigger's cadence clock)
    batches: int = 0
    #: live repartitions applied by the drift rebalancer
    rebalances: int = 0
    #: resident entries carried into new layouts, summed over rebalances
    migrated: int = 0
    #: requests served by degraded miss-through while their shard was
    #: down (cluster resilience; counted in ``requests`` too)
    degraded: int = 0
    #: shard dispatch attempts retried after a failure
    retried: int = 0
    #: requests that exhausted retries and failed over to miss-through
    failed_over: int = 0
    #: shard serves that exceeded the resilience timeout
    timeouts: int = 0
    #: topic-layer hits whose entry had outlived its TTL (or fell under
    #: an invalidation floor) at probe time, both stale policies
    expired: int = 0
    #: expired hits answered from the cached value anyway
    #: (``stale_policy="serve_stale_while_revalidate"``)
    stale_served: int = 0
    #: backend refreshes triggered by stale serves (after coalescing)
    revalidations: int = 0
    #: stale values served *without* a revalidation in flight -- must
    #: stay 0; a nonzero count means the freshness contract broke
    freshness_violations: int = 0
    #: invalidation events applied (slots zeroed for key events, one per
    #: topic/flush event for epoch-bump invalidations)
    invalidations: int = 0
    #: device->host array fetches ``serve`` issued (seven per batch on the
    #: one-call path: hit, layer, stale, value, then wrote, set_idx, way;
    #: five when the batch wrote nothing)
    readbacks: int = 0
    #: device->host copies started at dispatch, before any fetch waits:
    #: every host-bound output of the step (seven per batch on the
    #: one-call path, four on the unfused probe; none on the host
    #: engine's numpy outputs); warm-up is not counted
    early_copies: int = 0
    #: host->device arrays the serving path uploaded (ten per batch on the
    #: one-call path: four fill-plan arrays and six request arrays);
    #: warm-up uploads are not counted
    uploads: int = 0
    #: conflict-replay rounds, summed over batches: the largest number of
    #: requests of the padded batch that share one set (``max(seg_len)``
    #: of ``ops.plan_segments``), counted from the set indices the fill
    #: plan fetches -- on batches that wrote, on the host engine and the
    #: deferred-fill device path
    replay_rounds: int = 0
    #: conflict-replay lane-rounds, summed over the same batches: what
    #: the tiered replay runs at the padded length and that depth
    #: (``cache_ops.replay_lane_rounds``).  ``bp * replay_rounds`` is what
    #: a loop over all ``bp`` lanes runs (equal for ``bp <= 128``), so
    #: the ratio says how far the tiers narrowed the replay
    replay_lane_rounds: int = 0
    #: requests of this shard's slice, summed over the cluster batches in
    #: which it was the largest slice: counted by a sharded cluster's
    #: front end as it routes each batch (0 at one shard).  Summed over
    #: the shards it is the largest slice of every batch, so
    #: ``slice_max_requests / (requests / shards)`` is how far the
    #: largest slice exceeds an even split
    slice_max_requests: int = 0
    #: the online popularity tracker's state: exponentially-decayed served
    #: request counts per tracked topic (sorted id order) + a trailing
    #: no-topic bucket; shares memory with ``Broker.tracker`` and is None
    #: without a ``RebalanceSpec``
    topic_counts: Optional[np.ndarray] = None

    @property
    def hit_rate(self) -> float:
        return self.hits / self.requests if self.requests else 0.0


#: counters newer than some checkpoints: a restore reads them as 0
_LATER_COUNTERS = (
    "readbacks", "uploads", "replay_rounds", "replay_lane_rounds",
    "slice_max_requests", "early_copies",
)

Backend = Callable[[np.ndarray], np.ndarray]  # query ids -> values (B, V)


@dataclasses.dataclass
class HedgePolicy:
    """Straggler mitigation: re-dispatch a micro-batch that exceeds
    ``deadline_s`` to the next executor; first completed result wins."""

    deadline_s: float = 0.5
    max_hedges: int = 1


class Broker:
    def __init__(
        self,
        cache: STDDeviceCache,
        backends: Sequence[Backend],
        topic_of: Callable[[np.ndarray], np.ndarray],
        admission: Optional[Callable[[np.ndarray], np.ndarray]] = None,
        hedge: Optional[HedgePolicy] = None,
        microbatch: int = 256,
        coalesce: bool = True,
        spec: Optional[CacheSpec] = None,
        fused: bool = True,
        use_kernel: bool = False,
        engine: str = "auto",
        rebalance: Optional[RebalanceSpec] = None,
        bucket: Optional[BucketSpec] = None,
        defer_fill: Optional[bool] = None,
        freshness: Optional[FreshnessSpec] = None,
        fused_one_call: bool = True,
        aot_warmup: bool = False,
        device: Optional[jax.Device] = None,
    ):
        self.cache = cache
        #: declarative configuration this cache was compiled from (embedded
        #: in checkpoints so a restored broker can verify it serves the
        #: same cache)
        self.spec = spec
        if spec is not None and not spec.admission.trivial and admission is None:
            raise ValueError(
                "spec carries a non-trivial AdmissionSpec but no admission "
                "callable was provided; the broker would silently admit "
                "everything the spec says to filter"
            )
        self.backends = list(backends)
        self.topic_of = topic_of
        self.admission = admission
        self.hedge = hedge
        self.microbatch = microbatch
        #: in-flight request coalescing: duplicate keys inside one batch
        #: are dispatched to the backend only once (the duplicates are
        #: answered from the first result)
        self.coalesce = coalesce
        #: serve through the fused probe-and-commit path (one device call
        #: for a fully-hit batch); ``use_kernel`` routes the conflict
        #: resolution through the Pallas kernel (the interpreter on CPU hosts)
        self.fused = fused
        #: whether warmup() runs at every cache (re)bind -- construction
        #: and rebalance -- so no live request waits on a jax trace
        self.aot_warmup = bool(aot_warmup)
        if engine == "auto":
            # XLA CPU prices batch scatters/sorts far above numpy's native
            # ones; on accelerators the jnp/Pallas engines win
            engine = "device" if (use_kernel or jax.default_backend() != "cpu") else "host"
        if engine not in ("host", "device"):
            raise ValueError(f"engine must be auto|host|device, got {engine!r}")
        self.engine = engine
        self.use_kernel = use_kernel
        #: the device the cache state lives on (None: JAX's default).  A
        #: sharded cluster pins each shard to its own chip; every state the
        #: broker adopts -- built, restored, re-initialized or migrated --
        #: lands there before a jitted entry compiles against it
        self.device = device if engine == "device" else None
        self.state = self._placed(dict(cache.init_state))
        #: static-shape contract: pad batches up to shape buckets with the
        #: reserved pad key.  Auto (bucket=None): the jit-compiled device
        #: engine buckets (pow2), the host engine serves unpadded (numpy
        #: compiles nothing, padding would be pure overhead).
        if bucket is None:
            bucket = BucketSpec() if engine == "device" else BucketSpec(mode="none")
        self.bucket: Optional[BucketSpec] = bucket if bucket.enabled else None
        #: double-buffer the deferred value fill into the next fused call
        #: (device engine only; the host engine's in-place numpy fill is
        #: already a single cheap scatter)
        if defer_fill is None:
            defer_fill = engine == "device" and fused
        self.defer_fill = bool(defer_fill) and engine == "device" and fused
        #: one-dispatch device serving: the deferred fill, probe, commit
        #: and value gather share a single jitted entry point
        #: (``STDDeviceCache.serve_one_call``) -- ONE device call per
        #: served batch and one compiled shape per bucket.  False keeps
        #: the legacy ``fused``/``fused_fill`` pair (conformance-pinned).
        self.fused_one_call = bool(fused_one_call) and engine == "device" and fused
        #: compressed pending fill plan: (set_idx, way, values) of the
        #: last batch's inserts, applied inside the next fused call or by
        #: :meth:`flush`
        self._pending_fill: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None
        #: guards the pending-fill handoff: the pipelined cluster front
        #: end may overlap a shard's serve (pool thread) with a
        #: cluster-level flush/checkpoint from the caller thread, and the
        #: plan must be consumed exactly once whichever side lands it.
        #: Reentrant because _serve_fused calls flush() under the lock.
        self._fill_lock = threading.RLock()
        #: traces per jitted entry point (the wrapped python body only
        #: runs when jax traces a new shape) -- the compile-count
        #: regression tests pin this at O(#buckets)
        self.trace_counts: Dict[str, int] = {}
        #: device dispatches per jitted entry point (every call counts,
        #: traced or cached) -- the dispatch-count regression tests pin a
        #: served batch at exactly one on the fused-one-call path
        self.dispatch_counts: Dict[str, int] = {}
        #: bucket shapes already AOT-warmed against the current bound
        #: cache (reset on every rebind: fresh jits, fresh traces)
        self._warmed_shapes: set = set()
        #: rebalance cooldown/hysteresis runtime state (not checkpointed:
        #: a restored broker re-arms conservatively from scratch)
        self._last_rebalance_batch: Optional[int] = None
        self._rebalance_cooling = False
        self.stats = BrokerStats()
        #: drift-aware rebalancing: tracker observes every served batch's
        #: topics; every ``rebalance.every`` batches the tracked popularity
        #: is recompiled into a fresh proportional allocation and resident
        #: entries migrate through ``STDDeviceCache.repartition``
        self.rebalance_spec = rebalance
        self.tracker: Optional[PopularityTracker] = None
        if rebalance is not None:
            self.tracker = rebalance.to_tracker(cache.topic_ids)
            self.stats.topic_counts = self.tracker.counts
        #: freshness clock (TTL expiry + invalidation floors); None =
        #: entries never expire and every engine call carries zero
        #: epochs/floors -- bit-identical to pre-freshness serving
        self.freshness_spec = freshness
        self.freshness: Optional[FreshnessRuntime] = (
            FreshnessRuntime(freshness, cache.topic_ids)
            if freshness is not None
            else None
        )
        #: this broker's index in its cluster (trace metadata only)
        self.shard = 0
        self._bind_cache(cache)
        self._pool = ThreadPoolExecutor(max_workers=max(2, len(backends)))
        self._closed = False

    def _placed(self, state):
        """``state`` on this broker's device (as given when unpinned)."""
        if self.device is None:
            return state
        return jax.device_put(state, self.device)

    def _traced(self, name: str, fn):
        """Wrap ``fn`` so each jax trace bumps ``trace_counts[name]`` --
        the wrapper body only executes while tracing, so the counter is
        exactly the number of compiled shapes (cumulative across
        rebalances, which re-bind fresh jits).  The wrapper is named
        ``wrapper.<name>``, so its program compiles as
        ``jit_wrapper.<name>``."""
        counts = self.trace_counts

        def wrapper(*args, **kwargs):
            counts[name] = counts.get(name, 0) + 1
            return fn(*args, **kwargs)

        wrapper.__name__ = wrapper.__qualname__ = f"wrapper.{name}"
        return wrapper

    def _counted(self, name: str, fn):
        """Wrap a *jitted* entry so every call bumps
        ``dispatch_counts[name]`` -- unlike ``_traced`` this wrapper sits
        outside the jit boundary and runs on every dispatch, traced or
        cache-hit, so the counter is exactly the number of device calls
        issued through the entry point.  ``wrapper.__wrapped__`` is the
        jitted entry itself (``.lower(...)`` shows its compiled program)."""
        counts = self.dispatch_counts

        def wrapper(*args, **kwargs):
            counts[name] = counts.get(name, 0) + 1
            return fn(*args, **kwargs)

        wrapper.__wrapped__ = fn
        return wrapper

    def _fetch(self, x, copy: bool = False) -> np.ndarray:
        """``x`` as a numpy array; a device array's fetch counts in
        ``stats.readbacks`` (the host engine's arrays are numpy already).
        ``copy`` returns a writable copy."""
        if isinstance(x, jax.Array):
            self.stats.readbacks += 1
        return np.array(x) if copy else np.asarray(x)

    def _start_copies(self, *outputs) -> None:
        """Ask for each device array among a step's ``outputs`` to be
        copied to the host as soon as the step ends, counted in
        ``stats.early_copies``.  The transfers queue behind the step back
        to back; a later :meth:`_fetch` waits for its copy instead of
        issuing one, so the fetches pay one wait and not one round trip
        each.  Numpy outputs (the host engine's) pass untouched."""
        for x in outputs:
            if isinstance(x, jax.Array):
                x.copy_to_host_async()
                self.stats.early_copies += 1

    def _put(self, *arrays) -> list:
        """Upload host arrays for a serving call, counted in
        ``stats.uploads``."""
        self.stats.uploads += len(arrays)
        return [jnp.asarray(a) for a in arrays]

    def _bind_cache(self, cache: STDDeviceCache) -> None:
        """(Re)compile the jitted serving ops against ``cache`` -- run at
        construction and after every rebalance swaps the cache layout.
        With ``aot_warmup`` every rebind immediately AOT-compiles every
        bucket shape (:meth:`warmup`), so neither a fresh broker nor a
        just-rebalanced one ever makes a live request wait on a trace."""
        self.cache = cache
        # kernel request-tile size: the autotuner's persisted winner for
        # this backend at the top serving bucket (DEFAULT_BM without a
        # table); one static choice per bind keeps traces at O(#buckets)
        top = (
            self.bucket.padded_len(self.microbatch)
            if self.bucket is not None
            else self.microbatch
        )
        self._bm = autotune.best_bm(jax.default_backend(), top)
        self._probe = self._counted(
            "probe", jax.jit(self._traced("probe", cache.probe))
        )
        self._commit = self._counted(
            "commit",
            jax.jit(
                self._traced(
                    "commit",
                    functools.partial(cache.commit_vectorized, bm=self._bm),
                )
            ),
        )
        self._fused_step = self._counted(
            "fused",
            jax.jit(
                self._traced(
                    "fused",
                    functools.partial(
                        cache.probe_and_commit,
                        use_kernel=self.use_kernel,
                        bm=self._bm,
                    ),
                )
            ),
        )
        self._fused_fill_step = self._counted(
            "fused_fill",
            jax.jit(
                self._traced(
                    "fused_fill",
                    functools.partial(
                        cache.fill_probe_and_commit,
                        use_kernel=self.use_kernel,
                        bm=self._bm,
                    ),
                )
            ),
        )
        self._one_call_step = self._counted(
            "one_call",
            jax.jit(
                self._traced(
                    "one_call",
                    functools.partial(
                        cache.serve_one_call,
                        use_kernel=self.use_kernel,
                        bm=self._bm,
                    ),
                )
            ),
        )
        self._fill = self._counted(
            "fill", jax.jit(self._traced("fill", cache.fill_values))
        )
        self._warmed_shapes = set()
        if self.aot_warmup:
            self.warmup()

    def warmup_shapes(self, sizes: Sequence[int] = ()) -> List[int]:
        """The batch shapes the serving path can present to the jitted
        entries: every bucket boundary from ``padded_len(1)`` up to the
        microbatch's bucket (pow2 ladder), plus any explicit ``sizes``
        (bucket-snapped).  Without a bucket, just the (snapped) explicit
        sizes or the microbatch."""
        snap = (
            (lambda s: self.bucket.padded_len(s))
            if self.bucket is not None
            else (lambda s: int(s))
        )
        shapes = {snap(int(s)) for s in sizes if int(s) > 0}
        if self.bucket is not None:
            top = self.bucket.padded_len(self.microbatch)
            s = self.bucket.padded_len(1)
            while s <= top:
                shapes.add(s)
                s = self.bucket.padded_len(s + 1)
            shapes.add(top)
        elif not shapes:
            shapes.add(int(self.microbatch))
        return sorted(shapes)

    def warmup(self, sizes: Sequence[int] = ()) -> List[int]:
        """AOT-compile every serving entry point at every bucket shape,
        so no live request ever waits on a jax trace.

        Runs the *real* jitted entries (the same objects ``serve`` calls,
        so their traces land in the same jit caches and show up in
        ``trace_counts``) on all-pad batches: pads are inert in every
        engine, the outputs are discarded, state/stats/pending-fill are
        untouched, and nothing reaches a backend.  Idempotent per bound
        cache -- shapes already warmed since the last (re)bind are
        skipped, so calling it again (or serving after it) compiles
        nothing.  Returns the shapes warmed by *this* call; the host
        engine compiles nothing and returns ``[]``.
        """
        if self.engine == "host":
            return []
        warmed = []
        for s in self.warmup_shapes(sizes):
            if s in self._warmed_shapes:
                continue
            h_hi, h_lo = pack_hashes(np.full(s, PAD_H64, np.uint64))
            args = (
                jnp.asarray(h_hi),
                jnp.asarray(h_lo),
                jnp.asarray(np.full(s, self.cache.k, np.int32)),
                jnp.asarray(np.zeros(s, bool)),
                jnp.asarray(np.zeros(s, np.uint32)),
                jnp.asarray(np.zeros(s, np.uint32)),
            )
            plan = [jnp.asarray(a) for a in self._pad_plan(None, s)]
            if self.fused and self.fused_one_call:
                out = self._one_call_step(self.state, *plan, *args)
            elif self.fused:
                out = self._fused_step(self.state, *args)
                jax.block_until_ready(self._fused_fill_step(self.state, *plan, *args))
            else:
                out = self._probe(self.state, *args[:3], args[5])
                jax.block_until_ready(
                    self._commit(
                        self.state, *args[:3],
                        jnp.zeros((s, self.cache.cfg.value_dim), jnp.int32),
                        *args[3:],
                    )
                )
            jax.block_until_ready(out)
            # flush() pads a pending plan to its own bucket, so the
            # standalone fill sees the same shape ladder
            jax.block_until_ready(self._fill(self.state, *plan))
            self._warmed_shapes.add(s)
            warmed.append(s)
        return warmed

    @classmethod
    def from_spec(
        cls,
        spec,
        stats,
        backends: Sequence[Backend],
        topic_of: Optional[Callable[[np.ndarray], np.ndarray]] = None,
        value_fn=None,
        log=None,
        admitted: Optional[np.ndarray] = None,
        admission: Optional[Callable[[np.ndarray], np.ndarray]] = None,
        cache: Optional[STDDeviceCache] = None,
        device: Optional[jax.Device] = None,
    ) -> "Broker":
        """Compile a :class:`repro.serving.spec.ServingSpec` to one broker.

        The cache is built from ``spec.cache`` (static layer preloaded via
        ``value_fn``), the admission gate is compiled from the spec's
        ``AdmissionSpec`` (``log``/``admitted`` feed it; the ``admission``
        callable remains as a compatibility escape hatch), and every
        serving knob -- engine, fused, kernel, microbatch, coalescing,
        hedging -- comes from the spec.  ``spec.shards`` is ignored here:
        sharded deployments go through
        :meth:`repro.serving.cluster.Cluster.from_spec`, which hands each
        shard its slice of the cache via ``cache=`` so the rest of the
        spec compiles in exactly one place, and its ``device``.
        """
        if cache is None:
            cache = STDDeviceCache.from_spec(
                spec.cache, stats, value_fn=value_fn, ways=spec.ways,
                value_dim=spec.value_dim,
            )
        if admission is None:
            admission = spec.cache.admission.to_serving_gate(log=log, admitted=admitted)
        if topic_of is None:
            key_topic = np.asarray(stats.key_topic)
            topic_of = lambda q: key_topic[np.asarray(q, np.int64)]  # noqa: E731
        return cls(
            cache,
            backends,
            topic_of=topic_of,
            admission=admission,
            hedge=spec.hedge.to_policy() if spec.hedge is not None else None,
            microbatch=spec.microbatch,
            coalesce=spec.coalesce,
            spec=spec.cache,
            fused=spec.fused,
            use_kernel=spec.use_kernel,
            engine=spec.engine,
            rebalance=spec.rebalance,
            bucket=spec.bucket,
            freshness=spec.freshness,
            fused_one_call=spec.fused_one_call,
            aot_warmup=spec.aot_warmup,
            device=device,
        )

    # -- lifecycle -----------------------------------------------------------

    def close(self) -> None:
        """Apply any pending value fill and shut down the hedging
        executor.  Idempotent: a second close is a no-op, and ``serve``
        after close raises ``RuntimeError`` instead of failing deep in
        the executor."""
        if self._closed:
            return
        self.flush()
        self._pool.shutdown(wait=True)
        self._closed = True

    @property
    def closed(self) -> bool:
        return self._closed

    def __enter__(self) -> "Broker":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # -- serving -------------------------------------------------------------

    def advance_time(self, t_s: float) -> None:
        """Advance the freshness clock to virtual time ``t_s`` (seconds).

        The open-loop load harness calls this with each batch's arrival
        time before serving it; trace-driven callers without a clock can
        skip it (the clock stays at 0 and only invalidation floors can
        expire entries).  No-op without a :class:`FreshnessSpec`.
        """
        if self.freshness is not None:
            self.freshness.advance(t_s)

    def _freshness_arrays(self, parts: np.ndarray):
        """Per-request (min_epoch, epochs) for a (padded) batch.  Always
        arrays -- the jitted entry points keep one signature whether
        freshness is configured or not, so enabling it compiles zero new
        shapes (pinned by the trace-count regression tests)."""
        if self.freshness is None:
            z = np.zeros(len(parts), np.uint32)
            return z, z
        return self.freshness.min_epoch(parts), self.freshness.epochs(len(parts))

    def serve(
        self,
        query_ids: np.ndarray,
        topics: Optional[np.ndarray] = None,
        h64: Optional[np.ndarray] = None,
        seq: Optional[int] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Serve one batch of query ids -> (values (B, V), hit mask).

        ``topics`` short-circuits ``topic_of`` when the caller already
        routed the batch (the cluster's topic routing computes them
        once); ``h64`` likewise short-circuits ``splitmix64`` with the
        exact hash words the cluster routed on (bit-identical by
        construction -- the high word picks the shard, the low word the
        set).  ``seq`` is the batch's sequence number in the trace's
        span metadata (the cluster's; default this broker's batch count).

        Probes are atomic per batch: a duplicate key inside one batch is
        probed before its first occurrence commits, so it counts as a miss
        (both go to the backend).  Sequential (batch=1) serving matches the
        trace simulator request-for-request; production deployments would
        add in-flight request coalescing on top.

        The fused path makes a fully-hit batch a single device round-trip
        (probe + refresh in one call) and a batch with misses exactly two
        (plus the backend): the fused call additionally reserves insert
        slots, and the backend's results are scattered into them once they
        exist.  The admission policy therefore runs *before* the probe,
        over the whole batch (it must be a pure function of the query
        ids); only its decisions on missed queries have any effect.

        With a :class:`BucketSpec` (default on the device engine) the
        batch is padded up to its shape bucket with the reserved pad key
        before the device call -- pads never hit, never write, never
        reach the backend, and are sliced off the outputs, so bucketed
        serving is request-for-request identical to unpadded serving.
        """
        if self._closed:
            raise RuntimeError(
                "Broker.serve called after close(); the broker's executor "
                "is shut down -- build a new broker (or restore one from a "
                "checkpoint) to keep serving"
            )
        b = len(query_ids)
        bp = self.bucket.padded_len(b) if self.bucket is not None else b
        if seq is None:
            seq = self.stats.batches
        with TraceAnnotation("broker.serve", shard=self.shard, seq=seq, b=b, bp=bp):
            return self._serve(query_ids, topics, h64, seq)

    def _serve(self, query_ids, topics, h64, seq):
        b = len(query_ids)
        with TraceAnnotation("broker.prepare", seq=seq):
            if topics is None:
                topics = self.topic_of(query_ids)
            parts = np.asarray(self.cache.parts_for(np.asarray(topics)), np.int32)
            if h64 is None:
                h64 = splitmix64(query_ids)
            h_hi, h_lo = pack_hashes(h64)
            h_hi, h_lo, parts = self._pad_to_bucket(h_hi, h_lo, parts)
            min_ep, eps = self._freshness_arrays(parts)
            if self.fused:
                # the fused path admits over the whole batch before the
                # probe; pads are never admitted (the engines mask too)
                admit = np.zeros(len(h_hi), bool)
                admit[:b] = (
                    np.asarray(self.admission(query_ids), bool)
                    if self.admission is not None
                    else True
                )
        if self.fused:
            out = self._serve_fused(query_ids, parts, h_hi, h_lo, min_ep, eps, admit, seq)
            self._after_batch(topics)
            return out
        hit, layer, value, stale = self._probe(
            self.state, *self._put(h_hi, h_lo, parts, min_ep)
        )
        self._start_copies(hit, layer, value, stale)
        hit = self._fetch(hit)[:b]
        layer = self._fetch(layer)[:b]
        stale = self._fetch(stale)[:b]
        values = self._fetch(value, copy=True)[:b]  # pads sliced off
        self.stats.expired += int(stale.sum())
        swr = (
            self.freshness_spec is not None
            and self.freshness_spec.stale_policy == "serve_stale_while_revalidate"
        )
        if not swr:
            # policy "miss": an expired hit re-fetches before answering
            hit = hit & ~stale
            # tripwire, not bookkeeping: any expired entry still claiming
            # a fresh hit after the mask would be served stale under a
            # policy that forbids it -- structurally zero, counted so the
            # stat (and the launch/CI asserts on it) trip if a refactor
            # ever breaks the masking
            self.stats.freshness_violations += int((hit & stale).sum())

        miss_idx = np.flatnonzero(~hit)
        if len(miss_idx):
            with TraceAnnotation("broker.miss", seq=seq):
                if self.coalesce:
                    uniq, inverse = np.unique(query_ids[miss_idx], return_inverse=True)
                    self.stats.coalesced += len(miss_idx) - len(uniq)
                    miss_values = self._dispatch(uniq)[inverse]
                else:
                    miss_values = self._dispatch(query_ids[miss_idx])
                values[miss_idx] = miss_values
                admit = (
                    self.admission(query_ids[miss_idx])
                    if self.admission is not None
                    else np.ones(len(miss_idx), bool)
                )
                # expired entries refresh regardless of admission (they
                # are resident); only true misses consult the gate
                self.stats.admitted += int((admit & ~stale[miss_idx]).sum())
                self._commit_bucketed(
                    h_hi[miss_idx], h_lo[miss_idx], parts[miss_idx], miss_values,
                    admit, epochs=eps[miss_idx], min_epoch=min_ep[miss_idx],
                )
        # hits refresh recency too (exact LRU semantics); a stale
        # serve-while-revalidate hit additionally carries its backend
        # refresh value into the same commit (the engines only write
        # values where the entry is stale)
        hit_idx = np.flatnonzero(hit & (layer == 1))
        if len(hit_idx):
            commit_vals = values[hit_idx]
            if swr:
                reval = np.flatnonzero(stale[hit_idx])
                if len(reval):
                    self.stats.stale_served += len(reval)
                    uniq, inverse = np.unique(
                        query_ids[hit_idx][reval], return_inverse=True
                    )
                    self.stats.revalidations += len(uniq)
                    commit_vals = commit_vals.copy()
                    commit_vals[reval] = self._dispatch(uniq)[inverse]
            self._commit_bucketed(
                h_hi[hit_idx], h_lo[hit_idx], parts[hit_idx], commit_vals,
                np.zeros(len(hit_idx), bool),  # refresh only, never insert
                epochs=eps[hit_idx], min_epoch=min_ep[hit_idx],
            )
        self.stats.requests += b
        self.stats.hits += int(hit.sum())
        # layer is 0/1 only on hits (misses are -1), but mask with `hit`
        # anyway so both counters stay correct if the probe's layer
        # convention ever changes
        self.stats.static_hits += int(((layer == 0) & hit).sum())
        self.stats.topic_hits += int(((layer == 1) & hit).sum())
        self._after_batch(topics)
        return values, hit

    def _pad_to_bucket(self, h_hi, h_lo, parts):
        """Pad the request arrays up to the batch's shape bucket with the
        reserved pad key (routed at the dynamic partition; the pad never
        writes, so the partition choice only picks which set it probes)."""
        b = len(h_hi)
        bp = self.bucket.padded_len(b) if self.bucket is not None else b
        self.stats.padded += max(bp - b, 0)
        h_hi, h_lo, parts, _, _ = pad_batch(h_hi, h_lo, parts, self.cache.k, bp)
        return h_hi, h_lo, parts

    def _commit_bucketed(
        self, h_hi, h_lo, parts, values, admit, epochs=None, min_epoch=None
    ) -> None:
        """Unfused-path commit over a data-dependent subset (misses or hit
        refreshes), padded up to its bucket so the jitted commit compiles
        O(#buckets) shapes instead of one per subset length."""
        n = len(h_hi)
        bp = self.bucket.padded_len(n) if self.bucket is not None else n
        self.stats.padded += max(bp - n, 0)
        h_hi, h_lo, parts, values, admit = pad_batch(
            h_hi, h_lo, parts, self.cache.k, bp, values=values, admit=admit
        )
        eps = np.zeros(bp, np.uint32)
        minep = np.zeros(bp, np.uint32)
        if epochs is not None:
            eps[:n] = epochs
        if min_epoch is not None:
            minep[:n] = min_epoch
        self.state = self._commit(
            self.state, *self._put(h_hi, h_lo, parts, values, admit, eps, minep)
        )

    def _after_batch(self, topics: np.ndarray) -> None:
        """Post-serve bookkeeping: advance the batch clock, feed the drift
        tracker, and run a scheduled rebalance check at the spec cadence.
        Rebalancing happens strictly *between* batches."""
        if len(topics) == 0:
            return
        self.stats.batches += 1
        if self.tracker is None:
            return
        self.tracker.observe(np.asarray(topics))
        every = self.rebalance_spec.every
        if every and self.stats.batches % every == 0:
            self.rebalance()

    def _serve_fused(
        self, query_ids, parts, h_hi, h_lo, min_ep, eps, admit, seq
    ) -> Tuple[np.ndarray, np.ndarray]:
        """One fused device call per batch; the request arrays may carry a
        bucket-padded tail (``len(h_hi) >= len(query_ids)``) of reserved
        pad keys -- inert in the engines, sliced off the outputs here.
        ``min_ep``/``eps`` are the batch's freshness floors and write
        epochs (zeros without a spec); expiry rides the same call.

        On the device engine all seven host-bound outputs (``hit``,
        ``layer``, ``value``, ``stale``, ``set_idx``, ``wrote``, ``way``)
        start their copies to the host right after the call is enqueued
        (:meth:`_start_copies`), so ``broker.readback`` waits once for
        the step and the copies, and :meth:`_plan_fill` finds its arrays
        on the host; the new state stays on the device."""
        b = len(query_ids)
        bp = len(h_hi)
        if self.engine == "host":
            # the broker owns its state: the previous batch's arrays are
            # consumed in place (the host-engine analogue of jit donation)
            hit, layer, value, stale, self.state, (set_idx, wrote, way) = (
                self.cache.probe_and_commit_host(
                    self.state, h_hi, h_lo, parts, admit,
                    epochs=eps, min_epoch=min_ep, inplace=True,
                )
            )
        else:
            with self._fill_lock:
                pending = self._pending_fill
                if self.fused_one_call:
                    # one-dispatch serve: fill apply + probe + commit +
                    # value gather in a single jitted call (one Pallas
                    # kernel under use_kernel); an empty plan rides the
                    # same entry point, so every served batch is exactly
                    # ONE device dispatch and one compiled shape/bucket
                    if pending is not None and len(pending[0]) > bp:
                        self._flush(seq)  # plan larger than this bucket (rare)
                        pending = None
                    step = self._one_call_step
                elif pending is not None and 0 < len(pending[0]) <= bp:
                    # double-buffered fill: the previous batch's value
                    # scatter rides inside this fused call (applied before
                    # its probe), with the plan padded to this batch's
                    # bucket
                    step = self._fused_fill_step
                else:
                    self._flush(seq)  # plan larger than this bucket: standalone fill
                    step = self._fused_step
                with TraceAnnotation("broker.dispatch", seq=seq):
                    plan = () if step is self._fused_step else self._pad_plan(pending, bp)
                    hit, layer, value, stale, new_state, (set_idx, wrote, way) = step(
                        self.state,
                        *self._put(*plan, h_hi, h_lo, parts, admit, eps, min_ep),
                    )
                    self._start_copies(hit, layer, value, stale, set_idx, wrote, way)
                # the plan is consumed only once the call was issued
                # against it: a raise above leaves it pending, so a retry
                # or flush() still lands the values instead of losing them
                self._pending_fill = None
                self.state = new_state
        with TraceAnnotation("broker.readback", seq=seq):
            hit = self._fetch(hit)[:b]
            layer = self._fetch(layer)[:b]
            stale = self._fetch(stale)[:b]
            values = self._fetch(value, copy=True)  # (bp, V); sliced on return
        self.stats.expired += int(stale.sum())
        swr = (
            self.freshness_spec is not None
            and self.freshness_spec.stale_policy == "serve_stale_while_revalidate"
        )
        if not swr:
            # policy "miss": an expired hit re-fetches before answering --
            # the engines already reserved its slot for the refresh
            # (``wrote`` covers stale hits), so it joins the miss dispatch
            # and its backend value lands through the same deferred fill
            hit = hit & ~stale
            # tripwire mirroring the unfused path: stale serves under
            # policy "miss" are violations, structurally zero
            self.stats.freshness_violations += int((hit & stale).sum())
        with TraceAnnotation("broker.miss", seq=seq):
            miss_idx = np.flatnonzero(~hit)
            if len(miss_idx):
                if self.coalesce:
                    uniq, inverse = np.unique(query_ids[miss_idx], return_inverse=True)
                    self.stats.coalesced += len(miss_idx) - len(uniq)
                    values[miss_idx] = self._dispatch(uniq)[inverse]
                else:
                    values[miss_idx] = self._dispatch(query_ids[miss_idx])
                # expired entries refresh regardless of admission (they
                # are resident); only true misses consult the gate
                self.stats.admitted += int((admit[miss_idx] & ~stale[miss_idx]).sum())
            # serve-stale-while-revalidate: answer stale hits from the
            # cached value *now*, fetch the fresh one too, and route it
            # into the reserved slot via the deferred fill -- the caller
            # sees bounded staleness instead of backend latency
            fill_vals = values
            if swr:
                reval_idx = np.flatnonzero(hit & stale)
                if len(reval_idx):
                    self.stats.stale_served += len(reval_idx)
                    uniq, inverse = np.unique(query_ids[reval_idx], return_inverse=True)
                    self.stats.revalidations += len(uniq)
                    fill_vals = values.copy()
                    fill_vals[reval_idx] = self._dispatch(uniq)[inverse]
        with TraceAnnotation("broker.fill_plan", seq=seq):
            self._plan_fill(set_idx, wrote, way, fill_vals)
        self.stats.requests += b
        self.stats.hits += int(hit.sum())
        self.stats.static_hits += int(((layer == 0) & hit).sum())
        self.stats.topic_hits += int(((layer == 1) & hit).sum())
        return values[:b], hit

    def _plan_fill(self, set_idx, wrote, way, fill_vals) -> None:
        """Deferred fill: scatter the backend's results into the slots the
        fused call reserved (fresh hit refreshes kept their values;
        inserts and stale revalidations write).  Where the set indices
        come back to the host anyway, the replay's depth is counted from
        them: the largest number of the padded batch's requests in one
        set, and the lane-rounds the tiered replay runs at that depth.
        On the device engine the three fetches here (``wrote``, then
        ``set_idx`` and ``way`` where the batch wrote) read copies
        started at dispatch, before the backend ran."""
        wrote_np = self._fetch(wrote)
        if not wrote_np.any():
            return
        if self.engine == "device" and not self.defer_fill:
            self.state = self._fill(
                self.state, set_idx, wrote, way, *self._put(fill_vals)
            )
            return
        set_np = self._fetch(set_idx)
        depth = int(np.bincount(set_np).max())
        self.stats.replay_rounds += depth
        self.stats.replay_lane_rounds += replay_lane_rounds(len(set_np), depth)
        if self.engine == "host":
            self.state = self.cache.fill_values_host(
                self.state, set_np, wrote_np, way, fill_vals, inplace=True
            )
            return
        # double-buffer: hold the compressed plan; it lands inside the
        # next fused call (or flush()) -- key/stamp words are already
        # committed, only values lag, and the next probe reads them
        # post-fill by construction
        sel = np.flatnonzero(wrote_np)
        with self._fill_lock:
            self._pending_fill = (set_np[sel], self._fetch(way)[sel], fill_vals[sel])

    def _pad_plan(self, pending, bp: int):
        """Pad a compressed pending-fill plan up to ``bp`` entries (pads
        carry ``wrote=False``) in :meth:`STDDeviceCache.fill_values`
        argument order, as host arrays.  ``pending=None`` builds the
        all-inert plan the one-call entry point takes when nothing is
        pending -- same shapes/dtypes, zero writes -- so an idle serve
        compiles no extra shape."""
        if pending is None:
            f_set = np.zeros(0, np.int32)
            f_way = np.zeros(0, np.int32)
            f_vals = np.zeros((0, self.cache.cfg.value_dim), np.int32)
        else:
            f_set, f_way, f_vals = pending
        n = len(f_set)
        set_p = np.zeros(bp, np.int32)
        set_p[:n] = f_set
        way_p = np.zeros(bp, np.int32)
        way_p[:n] = f_way
        wrote_p = np.zeros(bp, bool)
        wrote_p[:n] = True
        vals_p = np.zeros((bp, f_vals.shape[1]), np.int32)
        vals_p[:n] = f_vals
        return set_p, wrote_p, way_p, vals_p

    def flush(self) -> None:
        """Apply a double-buffered pending value fill to the state now.

        Serving calls this automatically when a plan cannot ride the next
        fused call; checkpoints, rebalances and ``close()`` flush so the
        externally visible state is always complete.  Idempotent, and
        safe to overlap with a fused serve (the handoff lock makes the
        plan land exactly once whichever side consumes it).
        """
        self._flush()

    def _flush(self, seq: Optional[int] = None) -> None:
        """:meth:`flush`; ``seq`` is the batch that needed it (traces)."""
        with self._fill_lock:
            pending = self._pending_fill
            if pending is None:
                return
            meta = {} if seq is None else {"seq": seq}
            with TraceAnnotation("broker.flush", **meta):
                n = len(pending[0])
                bp = self.bucket.padded_len(n) if self.bucket is not None else n
                self.state = self._fill(
                    self.state, *self._put(*self._pad_plan(pending, bp))
                )
            # consumed only after the fill was issued: a raise above keeps
            # the plan pending, so a retrying caller (resilient dispatch)
            # flushes again instead of silently losing the values
            self._pending_fill = None

    # -- invalidation --------------------------------------------------------

    def invalidate(
        self,
        keys: Optional[np.ndarray] = None,
        topic: Optional[int] = None,
    ) -> int:
        """Invalidate cached results: by key, by topic, or everything.

        Exactly one of ``keys``/``topic`` must be given.  ``keys`` zeroes
        the matching resident slots host-side (control-plane traffic;
        returns the number of slots dropped).  ``topic`` is O(1): the
        topic's partition floor jumps above the current epoch and every
        resident entry of the partition expires at once -- no cache words
        move, the next probes simply see them stale (then refresh or
        re-fetch per the stale policy).  ``topic=-1`` flushes every
        partition.  Topic invalidation needs a :class:`FreshnessSpec`
        (the epoch machinery); key invalidation works on any broker.
        """
        if (keys is None) == (topic is None):
            raise ValueError("invalidate() takes exactly one of keys= or topic=")
        if topic is not None:
            if self.freshness is None:
                raise ValueError(
                    "topic invalidation uses epoch floors and needs a "
                    "FreshnessSpec; pass keys= for slot-zeroing invalidation "
                    "or build the broker with freshness configured"
                )
            if int(topic) < 0:
                self.freshness.flush_all()
            else:
                part = int(self.cache.parts_for(np.asarray([int(topic)]))[0])
                self.freshness.flush_topic(part)
            self.stats.invalidations += 1
            return 0
        keys = np.asarray(keys)
        if len(keys) == 0:
            return 0
        self.flush()  # pending values must land before slots are dropped
        h_hi, h_lo = pack_hashes(splitmix64(keys))
        parts = np.asarray(self.cache.parts_for(np.asarray(self.topic_of(keys))))
        self.state, n = self.cache.invalidate_keys(self.state, h_hi, h_lo, parts)
        self.stats.invalidations += n
        return n

    def _dispatch(self, miss_ids: np.ndarray) -> np.ndarray:
        """Micro-batched backend dispatch with hedging."""
        out = []
        for lo in range(0, len(miss_ids), self.microbatch):
            chunk = miss_ids[lo : lo + self.microbatch]
            out.append(self._call_hedged(chunk))
        return np.concatenate(out, axis=0)

    def _call_hedged(self, chunk: np.ndarray) -> np.ndarray:
        self.stats.backend_calls += 1
        if self.hedge is None or len(self.backends) == 1:
            return self.backends[0](chunk)
        fut = self._pool.submit(self.backends[0], chunk)
        done, _ = wait([fut], timeout=self.hedge.deadline_s, return_when=FIRST_COMPLETED)
        if done:
            return fut.result()
        # straggler: hedge to backups, first result wins
        futs = [fut]
        for backup in self.backends[1 : 1 + self.hedge.max_hedges]:
            self.stats.hedged_calls += 1
            futs.append(self._pool.submit(backup, chunk))
        while True:
            done, pending = wait(futs, return_when=FIRST_COMPLETED)
            for f in done:
                if f.exception() is None:
                    return f.result()
                futs = list(pending)
            if not futs:
                raise RuntimeError("all backends failed")

    # -- drift-aware rebalancing ----------------------------------------------

    def rebalance(self, force: bool = False) -> bool:
        """Recompute the topic allocation from tracked popularity and
        migrate resident entries into the new layout (live, between
        batches).

        Returns True when a migration ran.  Skips (returning False) when
        the tracker has no signal yet (``min_count``), when the target
        integer allocation equals the current one -- the no-op invariant:
        the cache state stays bit-identical on every engine -- or, unless
        ``force``, when the spec's cooldown (``min_interval`` batches
        since the last migration) or its (hysteresis-widened) divergence
        ``threshold`` gates the check.  After a migration the effective
        threshold is ``threshold + hysteresis`` until a scheduled check
        observes the divergence settled back at or below ``threshold`` --
        oscillating popularity then triggers one migration per swing
        *direction*, not one per check.
        """
        if self.tracker is None:
            raise ValueError(
                "broker was built without a RebalanceSpec; there is no "
                "popularity tracker to rebalance from"
            )
        sp = self.rebalance_spec
        if self.tracker.topic_mass < max(sp.min_count, 1e-9):
            return False  # no signal yet: keep the current allocation
        if (
            not force
            and sp.min_interval > 0
            and self._last_rebalance_batch is not None
            and self.stats.batches - self._last_rebalance_batch < sp.min_interval
        ):
            return False  # cooldown: too soon after the last migration
        pop = self.tracker.popularity()
        new_cfg = self.cache.cfg.rebalanced(pop)
        current = {int(t): int(c) for t, c in self.cache.cfg.topic_entries.items()}
        div = allocation_divergence(current, pop)
        # the settle check runs before the no-op early return: popularity
        # settling back to *exactly* the live allocation is the most
        # settled signal of all and must still re-arm the band
        if div <= sp.threshold:
            self._rebalance_cooling = False  # signal settled: re-arm
        if new_cfg == self.cache.cfg:
            return False
        if not force:
            eff = sp.threshold + (sp.hysteresis if self._rebalance_cooling else 0.0)
            if eff > 0.0 and div < eff:
                return False
        self.flush()  # a pending value fill must land before migration
        new_cache, new_state = self.cache.repartition(
            self.state, new_cfg,
            engine="host" if self.engine == "host" else "vec",
            bucket=self.bucket,
        )
        self.state = self._placed(new_state)
        self._bind_cache(new_cache)
        self.stats.rebalances += 1
        key_hi, _, _ = unpack_state({"ks": np.asarray(new_state["ks"])})
        self.stats.migrated += int((key_hi != 0).sum())
        self._last_rebalance_batch = self.stats.batches
        self._rebalance_cooling = sp.hysteresis > 0.0
        return True

    # -- fault tolerance -------------------------------------------------------

    def _stats_tree(self) -> Dict[str, np.ndarray]:
        """Checkpointable stats leaves (None fields -- an absent tracker --
        are dropped; npz cannot hold them and there is nothing to save)."""
        return {
            k: np.asarray(v)
            for k, v in dataclasses.asdict(self.stats).items()
            if v is not None
        }

    def save(self, ckpt_dir: str, step: int) -> str:
        self.flush()  # a pending value fill is part of the state
        tree = {"cache": self.state, "stats": self._stats_tree()}
        if self.freshness is not None:
            # the clock and invalidation floors are state: a restored
            # broker must keep enforcing TTLs from where it left off
            # (entries must not un-expire across a restart)
            tree["freshness"] = self.freshness.tree()
        if self.spec is not None:
            tree["spec_json"] = np.frombuffer(
                self.spec.to_json().encode("utf-8"), dtype=np.uint8
            )
        # the *live* allocation: a rebalanced broker's layout differs from
        # the spec's initial compile, and a restore must not revert it
        tree["alloc_json"] = np.frombuffer(
            self.cache.cfg.to_json().encode("utf-8"), dtype=np.uint8
        )
        return ckpt_lib.save(ckpt_dir, step, tree)

    def restore(self, ckpt_dir: str, step: Optional[int] = None) -> int:
        # a pending fill targets the pre-restore state's slots: drop it
        # (the checkpoint being adopted is complete by construction) and
        # re-arm the rebalance cooldown from scratch
        self._pending_fill = None
        self._last_rebalance_batch = None
        self._rebalance_cooling = False
        if step is None:
            step = ckpt_lib.latest_step(ckpt_dir)
            if step is None:
                raise FileNotFoundError(f"no checkpoints in {ckpt_dir}")
        # verify the embedded spec *before* loading state, so a
        # configuration mismatch reports as such rather than as a shape
        # mismatch deep inside the cache arrays
        if self.spec is not None:
            raw = ckpt_lib.load_leaf(ckpt_dir, step, "spec_json")
            if raw is not None:
                saved = CacheSpec.from_json(bytes(np.asarray(raw)).decode("utf-8"))
                if saved != self.spec:
                    raise ValueError(
                        "checkpoint was produced under a different CacheSpec: "
                        f"{saved.to_json()} != {self.spec.to_json()}"
                    )
        # the checkpoint's live allocation (still before touching arrays):
        # a broker restored mid-drift must keep serving with the rebalanced
        # layout, not silently revert to the spec's initial one.  The swap
        # is staged and only committed after the arrays load, so a failed
        # restore leaves the broker exactly as it was.
        pending_cache = None
        state_template = self.state
        raw = ckpt_lib.load_leaf(ckpt_dir, step, "alloc_json")
        if raw is not None:
            saved_cfg = DeviceCacheConfig.from_json(bytes(np.asarray(raw)).decode("utf-8"))
            if saved_cfg != self.cache.cfg:
                self._check_allocation_compatible(saved_cfg)
                pending_cache = STDDeviceCache(saved_cfg)
                state_template = dict(pending_cache.init_state)
                # the static layer is read-only and untouched by rebalance:
                # keep the preloaded arrays (their shapes validate the
                # checkpoint's)
                for k in ("static_hi", "static_lo", "static_value"):
                    state_template[k] = self.state[k]
        # a checkpoint that predates the tracker or the transfer counters
        # restores everything else: the tracker cold-starts from its zero
        # counts, the counters read 0
        stats_tree = self._stats_tree()
        saved_leaves = ckpt_lib.leaf_names(ckpt_dir, step)
        absent = [
            k for k in ("topic_counts",) + _LATER_COUNTERS
            if k in stats_tree and f"stats/{k}" not in saved_leaves
        ]
        for k in absent:
            del stats_tree[k]
        tree_like = {"cache": state_template, "stats": stats_tree}
        if (
            self.freshness is not None
            and ckpt_lib.load_leaf(ckpt_dir, step, "freshness/floors") is not None
        ):
            # freshness leaves restore only when both sides have them: a
            # pre-freshness checkpoint leaves the live clock untouched
            # (cold start), and a freshness checkpoint restored into a
            # TTL-less broker has no runtime to land in
            tree_like["freshness"] = self.freshness.tree()
        tree, got = ckpt_lib.restore(ckpt_dir, tree_like, step)
        if pending_cache is not None:
            self._bind_cache(pending_cache)
        if "freshness" in tree:
            self.freshness.load(tree["freshness"])
        self.state = jax.device_put(tree["cache"], self.device)
        for k, v in tree["stats"].items():
            if k == "topic_counts":
                # present only when a tracker exists (tree_like mirrors the
                # live stats); in place, so stats keeps sharing the array
                self.tracker.load(np.asarray(v, np.float64))
            else:
                setattr(self.stats, k, int(v))
        for k in absent:
            if k != "topic_counts":
                setattr(self.stats, k, 0)
        return got

    def _check_allocation_compatible(self, saved_cfg: DeviceCacheConfig) -> None:
        """Only the per-topic split may differ from the running config --
        anything else means the checkpoint belongs to a different
        deployment and fails informatively, like the spec checks."""
        cur = self.cache.cfg
        same_universe = (
            saved_cfg.total_entries == cur.total_entries
            and saved_cfg.ways == cur.ways
            and saved_cfg.value_dim == cur.value_dim
            and saved_cfg.static_entries == cur.static_entries
            and saved_cfg.dynamic_entries == cur.dynamic_entries
            and set(saved_cfg.topic_entries) == set(cur.topic_entries)
        )
        if not same_universe:
            raise ValueError(
                "checkpoint allocation is incompatible with this broker's "
                f"cache layout (not just a topic re-split): {saved_cfg.to_json()} "
                f"!= {cur.to_json()}"
            )
