"""Serving driver: the paper's system end-to-end.

Generates a calibrated query stream, trains the topic model, compiles a
declarative ``ServingSpec`` into a (possibly sharded) broker cluster,
and serves the test stream with a real model backend (reduced-config LM
scoring the query), printing hit rates per layer -- paper Fig. 2 as
runnable code, scaled out with ``--shards``/``--routing``.

  PYTHONPATH=src python -m repro.launch.serve --requests 50000 --entries 4096
  PYTHONPATH=src python -m repro.launch.serve --shards 4 --routing topic
  PYTHONPATH=src python -m repro.launch.serve --drift-phases 4 --rebalance 8
  PYTHONPATH=src python -m repro.launch.serve --open-loop --rate 100000 --burst 4
  PYTHONPATH=src python -m repro.launch.serve --open-loop --shards 4 \
      --fault-shard 2@0.1 --min-availability 1.0
  PYTHONPATH=src python -m repro.launch.serve --shards 4 --pipeline 8
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import math
import sys
import tempfile
import time

import jax
import numpy as np

from .. import configure_compile_cache
from ..configs.registry import get_arch
from ..core import CacheSpec
from ..core.spec import STRATEGIES
from ..core.fast import VecLog, VecStats
from ..loadgen import (
    ArrivalSpec,
    FaultInjectSpec,
    SLOSpec,
    run_open_loop,
    stamp_arrivals,
)
from ..serving import (
    BucketSpec,
    Cluster,
    DispatchSpec,
    FreshnessSpec,
    HedgeSpec,
    RebalanceSpec,
    ResilienceSpec,
    ServingSpec,
)
from ..models import transformer as tf
from ..querylog import DriftConfig, SynthConfig, generate, generate_drifting
from ..topics import run_pipeline


def _parse_fault_shard(s: str):
    """``N@T`` -> (shard N, FaultInjectSpec crashing at virtual time T)."""
    try:
        shard, t = s.split("@", 1)
        return int(shard), FaultInjectSpec(crash_at_s=float(t))
    except (ValueError, TypeError):
        raise argparse.ArgumentTypeError(
            f"--fault-shard wants N@T (shard index @ crash time in virtual "
            f"seconds), got {s!r}"
        )


def _parse_ttl_topic(s: str):
    """``TAU:SECONDS`` -> (topic id, TTL seconds)."""
    try:
        tau, sec = s.split(":", 1)
        ttl = float(sec)
        if not ttl > 0:
            raise ValueError("TTL must be > 0")
        return int(tau), ttl
    except (ValueError, TypeError):
        raise argparse.ArgumentTypeError(
            f"--ttl-topic wants TAU:SECONDS (topic id : TTL in virtual "
            f"seconds), got {s!r}"
        )


def _parse_fault_profile(s: str):
    """``N:JSON`` -> (shard N, FaultInjectSpec.from_json(JSON))."""
    try:
        shard, spec = s.split(":", 1)
        return int(shard), FaultInjectSpec.from_json(spec)
    except (ValueError, TypeError, KeyError) as e:
        raise argparse.ArgumentTypeError(
            f"--fault-profile wants N:JSON (shard index : FaultInjectSpec "
            f"JSON), got {s!r} ({e})"
        )


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--requests", type=int, default=50_000)
    ap.add_argument("--entries", type=int, default=4096)
    ap.add_argument(
        "--strategy", default="STDv_LRU", choices=("LRU",) + STRATEGIES,
        help="paper strategy compiled to the device cache via CacheSpec",
    )
    ap.add_argument("--f-s", type=float, default=0.5)
    ap.add_argument("--f-t", type=float, default=0.4)
    ap.add_argument("--f-ts", type=float, default=None)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--value-dim", type=int, default=8)
    ap.add_argument(
        "--shards", type=int, default=1,
        help="broker shards the cache's partition/set axis is split across",
    )
    ap.add_argument(
        "--routing", default="hash", choices=("hash", "topic"),
        help="query -> shard routing (topic routing moves whole partitions)",
    )
    ap.add_argument(
        "--pipeline", type=int, default=0, metavar="K",
        help="pipelined async dispatch: submit up to K batches through "
        "serve_async before draining, so per-shard work fuses across "
        "consecutive batches (0 = synchronous scatter-gather). Fused "
        "serves return identical values; cross-batch duplicate hits are "
        "accounted approximately (docs/serving.md)",
    )
    ap.add_argument(
        "--max-fuse", type=int, default=8,
        help="max queued batch segments one shard fuses into a single "
        "broker call when --pipeline is on",
    )
    ap.add_argument(
        "--bucket", default="auto", choices=("auto", "pow2", "off"),
        help="shape-bucketed batch padding (static-shape serving): the "
        "ragged tail batch and data-dependent shard slices pad up to a "
        "bucket with the reserved pad key instead of tracing a fresh "
        "shape. auto = pow2 on device engines, unpadded on the host "
        "engine; pow2 forces bucketing everywhere",
    )
    ap.add_argument(
        "--one-call", dest="one_call", action="store_true", default=True,
        help="serve via the fused one-dispatch kernel path: probe + commit "
        "+ value gather + deferred-fill apply in a single device call "
        "per batch (device engines only; the default)",
    )
    ap.add_argument(
        "--no-one-call", dest="one_call", action="store_false",
        help="use the legacy 2/3-dispatch serve path (separate fused "
        "probe+commit and fill calls)",
    )
    ap.add_argument(
        "--aot-warmup", action="store_true",
        help="AOT-compile every bucket shape at broker construction so no "
        "live request waits on a jit trace (docs/serving.md)",
    )
    ap.add_argument(
        "--rebalance", type=int, default=0, metavar="EVERY",
        help="drift-aware topic rebalancing: check every N served batches "
        "(0 = frozen allocation, the paper's setup)",
    )
    ap.add_argument(
        "--rebalance-decay", type=float, default=0.97,
        help="per-batch decay of the tracked topic popularity counts",
    )
    ap.add_argument(
        "--rebalance-threshold", type=float, default=0.0,
        help="min L1 share divergence before a scheduled check migrates",
    )
    ap.add_argument(
        "--open-loop", action="store_true",
        help="serve the test stream open-loop: seeded arrival process, "
        "deadline-driven batch coalescing via the spec's compiled "
        "BatchPolicySpec, per-request latency = queueing + measured "
        "service, SLO verdict (see docs/load_harness.md)",
    )
    ap.add_argument(
        "--rate", type=float, default=0.0,
        help="open-loop mean arrival rate in req/s (0 = 0.7x the batch "
        "policy's provisioned capacity)",
    )
    ap.add_argument(
        "--burst", type=float, default=1.0,
        help="open-loop burstiness: 1 = Poisson arrivals, >1 = on-off "
        "MMPP with this ON-state rate multiplier",
    )
    ap.add_argument(
        "--deadline-ms", type=float, default=0.0,
        help="override the batch policy's coalescing deadline (ms)",
    )
    ap.add_argument(
        "--slo-p99-ms", type=float, default=50.0,
        help="open-loop p99 latency SLO target (ms)",
    )
    ap.add_argument(
        "--arrival-seed", type=int, default=0,
        help="seed of the open-loop arrival process",
    )
    ap.add_argument(
        "--fault-shard", type=_parse_fault_shard, action="append", default=[],
        metavar="N@T",
        help="inject a permanent crash of shard N at virtual time T "
        "seconds (repeatable; open-loop only; enables the resilience "
        "layer so the crash degrades instead of failing)",
    )
    ap.add_argument(
        "--fault-profile", type=_parse_fault_profile, action="append",
        default=[], metavar="N:JSON",
        help="attach a full FaultInjectSpec (JSON) to shard N, e.g. "
        '2:{"error_every": 7} (repeatable; open-loop only)',
    )
    ap.add_argument(
        "--min-availability", type=float, default=0.0,
        help="exit nonzero when availability (fraction of served requests "
        "answered with backend-identical values) drops below this bound",
    )
    ap.add_argument(
        "--ttl-s", type=float, default=0.0,
        help="default result TTL in virtual seconds (0 = entries never "
        "expire).  Closed-loop runs map the synthetic log's time axis to "
        "seconds at one day = 86400s; open-loop runs use the arrival clock",
    )
    ap.add_argument(
        "--ttl-topic", type=_parse_ttl_topic, action="append", default=[],
        metavar="TAU:SECONDS",
        help="per-topic TTL override (repeatable), e.g. --ttl-topic 3:60",
    )
    ap.add_argument(
        "--stale-policy", default="miss",
        choices=("miss", "serve_stale_while_revalidate"),
        help="what an expired hit does: re-fetch before answering (miss) "
        "or answer stale now and revalidate through the deferred fill",
    )
    ap.add_argument(
        "--max-stale-rate", type=float, default=1.0,
        help="exit nonzero when the stale-serve rate (stale_served / "
        "requests) exceeds this bound (serve_stale_while_revalidate only)",
    )
    ap.add_argument(
        "--drift-phases", type=int, default=0,
        help="serve a piecewise-stationary drift stream with this many "
        "popularity phases (oracle topics, no LDA) instead of the "
        "calibrated stationary log",
    )
    return ap


def spec_from_args(args, faults=()) -> ServingSpec:
    """The declarative serving spec the CLI's arguments describe."""
    return ServingSpec(
        cache=CacheSpec.from_strategy(
            args.strategy, args.entries, f_s=args.f_s, f_t=args.f_t, f_ts=args.f_ts
        ),
        shards=args.shards,
        routing=args.routing,
        microbatch=args.batch,
        value_dim=args.value_dim,
        # auto (None): device engines bucket pow2, the host engine serves
        # unpadded; the ragged tail batch below is served through bucket
        # padding instead of a separately-traced shape either way
        bucket={
            "auto": None,
            "pow2": BucketSpec(),
            "off": BucketSpec(mode="none"),
        }[args.bucket],
        hedge=HedgeSpec(deadline_s=2.0),
        fused_one_call=args.one_call,
        aot_warmup=args.aot_warmup,
        dispatch=(
            DispatchSpec(max_fuse=args.max_fuse)
            if args.pipeline > 0
            else None
        ),
        rebalance=(
            RebalanceSpec(
                every=args.rebalance,
                decay=args.rebalance_decay,
                threshold=args.rebalance_threshold,
            )
            if args.rebalance > 0
            else None
        ),
        # fault injection implies the resilience layer: without it any
        # injected fault would simply propagate and kill the run
        resilience=ResilienceSpec(probe_interval_s=0.005) if faults else None,
        freshness=(
            FreshnessSpec(
                ttl_s=args.ttl_s if args.ttl_s > 0 else math.inf,
                topic_ttl_s=dict(args.ttl_topic),
                stale_policy=args.stale_policy,
            )
            if (args.ttl_s > 0 or args.ttl_topic)
            else None
        ),
    )


@dataclasses.dataclass
class Stream:
    """A generated query stream with its trained topic assignment."""

    synth: object  # the generator's output (keys, timestamps, topics)
    log: VecLog  # train/test split over the keys
    stats: VecStats  # training statistics the cache is compiled from
    key_topic: np.ndarray  # query id -> topic (-1 = no topic)

    def topic_of(self, q: np.ndarray) -> np.ndarray:
        return self.key_topic[q]


def build_stream(requests: int, drift_phases: int = 0) -> Stream:
    """The seeded query stream: the calibrated log with LDA topics, or a
    piecewise-stationary drift stream with ``drift_phases`` phases."""
    if drift_phases > 0:
        dcfg = DriftConfig(
            n_requests=requests,
            n_topics=16,
            queries_per_topic=max(requests // 64, 64),
            n_notopic_queries=max(requests // 40, 64),
            n_phases=drift_phases,
            seed=11,
        )
        synth = generate_drifting(dcfg)
        # oracle topics: the drift generator emits no clicked documents, so
        # the LDA pipeline has nothing to train on -- and the scenario under
        # test is the allocation's staleness, not topic discovery
        log = VecLog(
            keys=synth.keys,
            n_train=requests // drift_phases,
            key_topic=synth.true_topic,
        )
        return Stream(synth, log, VecStats.from_log(log), synth.true_topic)
    cfg = SynthConfig(
        n_requests=requests,
        n_topics=16,
        n_topical_queries=requests // 10,
        n_notopic_queries=requests // 20,
        vocab_size=512,
        seed=11,
    )
    synth = generate(cfg)
    pipe = run_pipeline(synth, train_frac=0.5, lda_iters=15, lda_subsample=5_000)
    return Stream(synth, pipe.log, pipe.stats, pipe.assignment.key_topic)


def model_scores(params, tokens, mcfg, value_dim: int):
    """The backend's answer for a batch of query token windows: the top
    ``value_dim`` token ids of the LM's last-position logits."""
    logits, _ = tf.forward(params, tokens, mcfg)
    return jax.lax.top_k(logits[:, -1], value_dim)[1]


def build_backend(arch: str, value_dim: int, chunk: int):
    """The miss backend: a reduced-config LM scoring each query, answering
    ``value_dim`` doc ids per query.

    Every call runs in ``chunk``-row pieces, the last one padded, so the
    model compiles one shape and its activations stay bounded however
    many rows arrive at once -- the static-layer preload hands it the
    whole static key set in one call."""
    mcfg = get_arch(arch).smoke_config
    params = tf.init_params(jax.random.PRNGKey(0), mcfg)
    scores = jax.jit(
        functools.partial(model_scores, mcfg=mcfg, value_dim=value_dim)
    )

    def backend(qids: np.ndarray) -> np.ndarray:
        qids = np.asarray(qids, np.int64)
        out = np.empty((len(qids), value_dim), np.int32)
        for lo in range(0, len(qids), chunk):
            part = qids[lo : lo + chunk]
            # query text stub: derive a token window from the query id
            tokens = np.zeros((chunk, 8), np.int32)
            tokens[: len(part)] = (
                part[:, None] * 31 + np.arange(8)[None, :]
            ) % mcfg.vocab_size
            out[lo : lo + len(part)] = np.asarray(scores(params, tokens))[: len(part)]
        return out

    return backend


def build_cluster(spec: ServingSpec, stream: Stream, backend) -> Cluster:
    """Compile ``spec`` into a cluster over ``stream``'s training stats,
    with ``backend`` answering misses and preloading the static layer."""
    return Cluster.from_spec(
        spec, stream.stats, [backend], topic_of=stream.topic_of,
        value_fn=backend,
    )


def main(argv=None) -> int:
    ap = build_parser()
    args = ap.parse_args(argv)

    faults = list(args.fault_shard) + list(args.fault_profile)
    if faults and not args.open_loop:
        ap.error("--fault-shard/--fault-profile need --open-loop (fault "
                 "schedules run on the open-loop virtual clock)")
    for shard, _ in faults:
        if not 0 <= shard < args.shards:
            ap.error(f"--fault shard index {shard} out of range for "
                     f"--shards {args.shards}")

    # build the declarative spec up front so configuration errors (e.g. an
    # SDC-section strategy without --f-ts, or a bad shard/routing combo)
    # fail before the expensive log generation; the same spec drives the
    # exact and reuse-distance engines bit-identically
    spec = spec_from_args(args, faults)
    print(f"serving spec: {spec.to_json()}")
    configure_compile_cache()

    if args.drift_phases > 0:
        print(f"generating drift stream ({args.drift_phases} popularity phases) ...")
    else:
        print("generating calibrated query log + LDA topics ...")
    stream = build_stream(args.requests, args.drift_phases)
    synth, log = stream.synth, stream.log
    backend = build_backend(args.arch, args.value_dim, chunk=args.batch)

    test = log.test_keys
    with build_cluster(spec, stream, backend) as cluster:
        if args.open_loop:
            policy = spec.compiled_batch_policy()
            if args.deadline_ms > 0:
                policy = dataclasses.replace(
                    policy, deadline_us=args.deadline_ms * 1e3
                )
            rate = args.rate if args.rate > 0 else 0.7 * policy.capacity_rps()
            if args.burst > 1.0:
                arrivals = ArrivalSpec(
                    process="onoff", rate=rate, burst=args.burst,
                    seed=args.arrival_seed,
                )
            else:
                arrivals = ArrivalSpec(
                    process="poisson", rate=rate, seed=args.arrival_seed
                )
            print(
                f"open-loop: {arrivals.process} arrivals at {rate:.0f} req/s "
                f"(provisioned capacity {policy.capacity_rps():.0f} req/s), "
                f"deadline {policy.deadline_us/1e3:.2f}ms, "
                f"max_batch {policy.max_batch}, queue {policy.max_queue} "
                f"({policy.overflow})"
            )
            workload = stamp_arrivals(test, arrivals)
            ckpt_tmp = None
            if faults:
                # a pre-stream checkpoint is what a crashed shard
                # warm-restarts from (checksum-verified; docs/resilience.md)
                ckpt_tmp = tempfile.TemporaryDirectory(prefix="serve_ckpt_")
                cluster.save(ckpt_tmp.name, step=0)
                for shard, fspec in faults:
                    cluster.inject_shard_faults(shard, fspec)
                    print(f"fault injected on shard {shard}: {fspec.to_json()}")
            res = run_open_loop(
                workload, cluster, policy, collect=bool(faults),
                pipeline=args.pipeline or None,
            )
            rep = res.report()
            print(
                f"served {rep.served}/{rep.n} "
                f"(shed {rep.shed}, deferred {rep.deferred}) "
                f"throughput={rep.achieved_rps:.0f} req/s "
                f"(measured service {rep.service_rps:.0f} req/s) "
                f"hit_rate={rep.hit_rate:.4f} pad_overhead={rep.pad_overhead:.2%}"
            )
            print(
                f"latency ms: p50={rep.p50_ms:.3f} p90={rep.p90_ms:.3f} "
                f"p99={rep.p99_ms:.3f} p99.9={rep.p999_ms:.3f} "
                f"(queueing p99={rep.queue_p99_ms:.3f})"
            )
            verdict = SLOSpec(p99_ms=args.slo_p99_ms).evaluate(rep)
            print(verdict.describe())
            fresh_ok = _report_freshness(
                spec, cluster.stats, args.max_stale_rate
            )
            available = True
            if faults:
                served = ~np.isnan(res.queue_s)
                oracle = backend(workload.keys[served])
                availability = (
                    float(np.all(res.values[served] == oracle, axis=1).mean())
                    if served.any()
                    else 0.0
                )
                s = cluster.stats
                recoveries = sum(
                    h.counters.recoveries for h in cluster.shard_health
                )
                spans = [
                    (i, sp)
                    for i, h in enumerate(cluster.shard_health)
                    for sp in h.down_spans()
                ]
                recovery_s = max(
                    (sp[1] - sp[0] for _, sp in spans if sp[1] is not None),
                    default=float("nan"),
                )
                print(
                    f"resilience: availability={availability:.4f} "
                    f"degraded={s.degraded} "
                    f"({s.degraded / max(s.requests, 1):.2%} of requests) "
                    f"retried={s.retried} failed_over={s.failed_over} "
                    f"recoveries={recoveries} recovery_s={recovery_s:.4f}"
                )
                for i, (down_at, up_at) in spans:
                    up = f"{up_at:.4f}" if up_at is not None else "open"
                    print(f"  shard {i} outage: down@{down_at:.4f}s -> {up}")
                available = availability >= args.min_availability
                if not available:
                    print(
                        f"AVAILABILITY FAIL: {availability:.4f} < "
                        f"--min-availability {args.min_availability:.4f}"
                    )
                ckpt_tmp.cleanup()
            return 0 if (verdict.ok and available and fresh_ok) else 1
        # time serving only: construction above preloads the static layer
        # through the model backend and warms per-shard jits, which would
        # otherwise skew the shards=1 vs shards=N comparison
        t0 = time.time()
        # closed-loop freshness clock: the synthetic log's time axis (days
        # for the calibrated log, one "day" per phase for drift) mapped to
        # virtual seconds, advanced to each batch's first arrival
        ts_test = (
            np.asarray(synth.timestamps, np.float64)[log.n_train :] * 86400.0
            if spec.freshness is not None
            else None
        )
        # serve every batch including the ragged tail, so the reported hit
        # rate covers the whole test stream
        starts = list(range(0, len(test), args.batch))
        if args.pipeline > 1:
            # pipelined drive: submit a group before draining so per-shard
            # work fuses across batches; the freshness clock (if any)
            # advances to the group's last batch up front, since queued
            # batches serve at submission time
            for g in range(0, len(starts), args.pipeline):
                grp = starts[g : g + args.pipeline]
                if ts_test is not None:
                    cluster.advance_time(float(ts_test[grp[-1]]))
                futs = [
                    cluster.serve_async(test[lo : lo + args.batch])
                    for lo in grp
                ]
                for f in futs:
                    f.result()
        else:
            for lo in starts:
                if ts_test is not None:
                    cluster.advance_time(float(ts_test[lo]))
                cluster.serve(test[lo : lo + args.batch])
        dt = time.time() - t0
        s = cluster.stats
        assert s.requests == len(test)
        print(
            f"served {s.requests} requests in {dt:.1f}s "
            f"({s.requests/dt:.0f} req/s incl. backend)"
        )
        print(
            f"hit_rate={s.hit_rate:.4f} static_hits={s.static_hits} "
            f"topic_hits={s.topic_hits} backend_calls={s.backend_calls} "
            f"hedged={s.hedged_calls}"
        )
        # pad overhead of the static-shape contract: device-batch slots
        # spent on the reserved pad key (ragged tail + shard slices)
        slot_total = s.requests + s.padded
        print(
            f"bucketing: padded={s.padded} real={s.requests} "
            f"pad_overhead={s.padded / max(slot_total, 1):.2%} of "
            f"{slot_total} device-batch slots; "
            f"jit traces per entry point: {cluster.trace_counts or '(host engine: none)'}; "
            f"device dispatches per entry point: "
            f"{cluster.dispatch_counts or '(host engine: none)'}"
        )
        if args.rebalance > 0:
            print(
                f"rebalances={s.rebalances} migrated_entries={s.migrated} "
                f"(check every {args.rebalance} batches, "
                f"decay={args.rebalance_decay})"
            )
        fresh_ok = _report_freshness(spec, s, args.max_stale_rate)
        if args.shards > 1:
            for i, ss in enumerate(cluster.shard_stats):
                print(
                    f"  shard {i}: requests={ss.requests} "
                    f"hit_rate={ss.hit_rate:.4f}"
                )
    return 0 if fresh_ok else 1


def _report_freshness(spec: ServingSpec, s, max_stale_rate: float) -> bool:
    """Print the freshness stats line; False = the run must exit nonzero
    (stale-serve bound exceeded, or the zero-violation tripwire fired)."""
    if spec.freshness is None:
        return True
    stale_rate = s.stale_served / max(s.requests, 1)
    print(
        f"freshness: expired={s.expired} stale_served={s.stale_served} "
        f"(stale_rate={stale_rate:.4f}) revalidations={s.revalidations} "
        f"violations={s.freshness_violations} invalidations={s.invalidations}"
    )
    ok = True
    if stale_rate > max_stale_rate:
        print(
            f"FRESHNESS FAIL: stale_rate {stale_rate:.4f} > "
            f"--max-stale-rate {max_stale_rate:.4f}"
        )
        ok = False
    if s.freshness_violations:
        print(
            f"FRESHNESS FAIL: {s.freshness_violations} stale values served "
            "without a revalidation in flight"
        )
        ok = False
    return ok


if __name__ == "__main__":
    sys.exit(main())
