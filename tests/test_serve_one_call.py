"""One-dispatch serve: dispatch-count regression, AOT warmup, conformance.

Pins the three contracts PR 10 introduced:

* **Dispatch counts** -- on the default device path a served batch is
  exactly ONE device call (``one_call``): the previous batch's deferred
  fill, the probe, the commit and the value gather share a single jitted
  entry point.  A fully-hit batch leaves no pending fill, so its delta
  in ``Broker.dispatch_counts`` is exactly ``{"one_call": +1}``.
* **AOT warmup** -- ``Broker.warmup`` compiles every bucket shape at
  construction, so a live ragged stream adds zero traces afterwards, on
  a bare broker and on a shards=1 cluster, and warmup is idempotent.
* **Conformance** -- one-call serving is request-for-request identical
  to the legacy 2/3-dispatch fused path and to the host engine, with
  freshness on and off; and the fused kernel (`serve_fused_op`) is
  bit-exact against the sequential numpy oracle (`serve_fused_ref`)
  under ragged final tiles, all-pad batches, all-static-hit batches and
  duplicate keys (hypothesis sweeps the same space harder when
  installed).
"""
import json

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import CacheSpec, VecLog, VecStats
from repro.kernels.cache_ops import (
    fill_winner_slots,
    pack_words,
    serve_fused_op,
    serve_fused_ref,
    unpack_epoch,
    unpack_words,
)
from repro.serving import (
    Broker,
    BucketSpec,
    Cluster,
    DeviceCacheConfig,
    FreshnessSpec,
    PAD_H64,
    STDDeviceCache,
    ServingSpec,
    pack_hashes,
    splitmix64,
)
from repro.serving import autotune


def _backend(value_dim):
    def backend(qids):
        return np.tile(np.asarray(qids)[:, None], (1, value_dim)).astype(np.int32)

    return backend


RAGGED = [64, 33, 57, 7, 128, 1, 99, 17, 64]


def _make_broker(engine, bucket, freshness=None, **kw):
    rng = np.random.default_rng(0)
    topic_of_q = rng.integers(-1, 4, size=500)
    cfg = DeviceCacheConfig.build(
        128, f_s=0.1, f_t=0.6,
        topic_distinct={t: 10 + t for t in range(4)}, ways=4, value_dim=2,
    )
    backend = _backend(2)
    static_q = np.array([0, 1])
    cache = STDDeviceCache(
        cfg, static_hashes=splitmix64(static_q), static_values=backend(static_q)
    )
    return Broker(
        cache, [backend], lambda q: topic_of_q[q], engine=engine,
        bucket=bucket, freshness=freshness, **kw,
    )


# -- conformance: one-call == legacy == host ---------------------------------


@pytest.mark.parametrize("fresh", [False, True])
def test_one_call_matches_legacy_and_host_request_for_request(fresh):
    spec = FreshnessSpec(ttl_s=5.0) if fresh else None
    ref = _make_broker("host", BucketSpec(mode="none"), freshness=spec)
    one = _make_broker(
        "device", BucketSpec(min_size=8), freshness=spec, fused_one_call=True
    )
    legacy = _make_broker(
        "device", BucketSpec(min_size=8), freshness=spec, fused_one_call=False
    )
    assert one.fused_one_call and not legacy.fused_one_call
    rng = np.random.default_rng(2)
    t = 0.0
    for n in RAGGED * 2:
        q = rng.integers(0, 500, size=n)
        t += 1.0
        for b in (ref, one, legacy):
            b.advance_time(t)
        v0, h0 = ref.serve(q)
        v1, h1 = one.serve(q)
        v2, h2 = legacy.serve(q)
        assert np.array_equal(v1, v0) and np.array_equal(h1, h0), n
        assert np.array_equal(v2, v0) and np.array_equal(h2, h0), n
    for b in (one, legacy):
        for f in ("requests", "hits", "static_hits", "topic_hits", "admitted",
                  "backend_calls", "expired"):
            assert getattr(b.stats, f) == getattr(ref.stats, f), f
    # after a flush the deferred fills have landed: cached values identical
    one.flush()
    legacy.flush()
    assert np.array_equal(
        np.asarray(one.state["value"]), np.asarray(ref.state["value"])
    )
    assert np.array_equal(
        np.asarray(one.state["value"]), np.asarray(legacy.state["value"])
    )
    assert np.array_equal(np.asarray(one.state["ks"]), np.asarray(legacy.state["ks"]))
    for b in (ref, one, legacy):
        b.close()


# -- dispatch-count regression -----------------------------------------------


def test_fully_hit_batch_is_exactly_one_device_dispatch():
    broker = _make_broker("device", BucketSpec(min_size=8))
    rng = np.random.default_rng(4)
    q = rng.integers(0, 500, size=64)
    broker.serve(q)  # misses populate + leave a pending fill
    _, h = broker.serve(q)  # fills ride in; surviving keys are resident
    q = q[h]  # resident, just-refreshed keys: the next serve fully hits
    assert len(q) > 8
    before = dict(broker.dispatch_counts)
    v, h = broker.serve(q)  # fully hit, no pending fill
    assert h.all()
    after = dict(broker.dispatch_counts)
    delta = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    assert delta == {k: 0 for k in delta} | {"one_call": 1}, delta
    # the legacy fused pair stays conformant and is pinned to its own
    # entry points (no one_call dispatches ever)
    legacy = _make_broker("device", BucketSpec(min_size=8), fused_one_call=False)
    legacy.serve(q)
    legacy.serve(q)
    _, h = legacy.serve(q)
    assert h.all()
    assert legacy.dispatch_counts.get("one_call", 0) == 0
    assert legacy.dispatch_counts.get("fused", 0) > 0
    # the unfused path prices the same fully-hit batch at 2 device calls
    # (probe + hit-refresh commit) -- the dispatch the one-call path saves
    unfused = _make_broker("device", BucketSpec(min_size=8), fused=False)
    unfused.serve(q)
    unfused.serve(q)
    before = dict(unfused.dispatch_counts)
    _, h = unfused.serve(q)
    assert h.all()
    after = dict(unfused.dispatch_counts)
    assert sum(after.values()) - sum(before.values()) >= 2, (before, after)
    broker.close()
    legacy.close()
    unfused.close()


def test_aot_warmup_leaves_zero_cold_traces_broker():
    broker = _make_broker("device", BucketSpec(min_size=8), aot_warmup=True)
    warmed = sorted(broker._warmed_shapes)
    assert warmed == broker.warmup_shapes()
    frozen = dict(broker.trace_counts)
    assert frozen  # warmup actually compiled something
    assert broker.warmup() == []  # idempotent: nothing left to warm
    rng = np.random.default_rng(6)
    for n in RAGGED:
        broker.serve(rng.integers(0, 500, size=n))
    assert dict(broker.trace_counts) == frozen, (frozen, broker.trace_counts)
    assert broker.dispatch_counts.get("one_call", 0) >= len(RAGGED)
    broker.close()


def test_aot_warmup_leaves_zero_cold_traces_cluster():
    rng = np.random.default_rng(8)
    nq, n = 500, 4000
    keys = rng.integers(0, nq, size=n).astype(np.int64)
    topic = rng.integers(-1, 4, size=nq).astype(np.int64)
    stats = VecStats.from_log(VecLog(keys=keys, n_train=n // 2, key_topic=topic))
    backend = _backend(2)
    spec = ServingSpec(
        cache=CacheSpec.from_strategy("STDv_LRU", 256, f_s=0.2, f_t=0.6),
        value_dim=2, shards=1, engine="device",
        bucket=BucketSpec(min_size=8), aot_warmup=True,
    )
    assert ServingSpec.from_json(spec.to_json()) == spec  # knob round-trips
    with Cluster.from_spec(spec, stats, [backend], value_fn=backend) as cluster:
        frozen = dict(cluster.trace_counts)
        assert frozen
        assert cluster.warmup() == []
        for sz in RAGGED:
            cluster.serve(rng.integers(0, nq, size=sz))
        assert dict(cluster.trace_counts) == frozen
        assert cluster.dispatch_counts.get("one_call", 0) >= len(RAGGED)


def test_warmup_does_not_touch_state_or_stats():
    broker = _make_broker("device", BucketSpec(min_size=8))
    ks0 = np.asarray(broker.state["ks"]).copy()
    val0 = np.asarray(broker.state["value"]).copy()
    warmed = broker.warmup()
    assert warmed == broker.warmup_shapes()
    assert np.array_equal(np.asarray(broker.state["ks"]), ks0)
    assert np.array_equal(np.asarray(broker.state["value"]), val0)
    assert broker.stats.requests == 0 and broker.stats.hits == 0
    assert broker._pending_fill is None
    broker.close()


# -- kernel property tests vs the numpy oracle -------------------------------


def _rand_state(rng, s=16, w=4, v=3, fill=0.5):
    n = int(s * w * fill)
    hi = np.zeros((s, w), np.uint64)
    flat = rng.choice(s * w, size=n, replace=False)
    keys = rng.integers(1, 400, size=n)
    h64 = splitmix64(keys)
    hi64 = np.zeros(s * w, np.uint64)
    hi64[flat] = h64
    key_hi = (hi64 >> np.uint64(32)).astype(np.uint32).reshape(s, w)
    key_lo = (hi64 & np.uint64(0xFFFFFFFF)).astype(np.uint32).reshape(s, w)
    stamp = rng.integers(0, 50, size=(s, w)).astype(np.int32)
    epoch = rng.integers(0, 4, size=(s, w)).astype(np.uint32)
    value = rng.integers(0, 1000, size=(s, w, v)).astype(np.int32)
    return key_hi, key_lo, stamp, epoch, value


def _rand_batch(rng, b, s, v, pad_frac=0.1, static_frac=0.1, dup=True):
    qids = rng.integers(0, 400, size=b)
    if dup and b > 4:  # force in-batch duplicates
        qids[b // 2 :] = rng.choice(qids[: b // 2], size=b - b // 2)
    h64 = splitmix64(qids)
    pad = rng.random(b) < pad_frac
    h64[pad] = PAD_H64
    h_hi, h_lo = pack_hashes(h64)
    set_idx = rng.integers(0, s, size=b).astype(np.int32)
    admit = rng.random(b) < 0.7
    static_hit = (rng.random(b) < static_frac) & ~pad
    epochs = rng.integers(0, 4, size=b).astype(np.uint32)
    minep = rng.integers(0, 3, size=b).astype(np.uint32)
    f_set = rng.integers(0, s + 2, size=b).astype(np.int32)
    f_wrote = rng.random(b) < 0.4
    f_way = rng.integers(0, 5, size=b).astype(np.int32)
    f_vals = rng.integers(0, 1000, size=(b, v)).astype(np.int32)
    return (h_hi, h_lo, set_idx, admit, static_hit, epochs, minep,
            f_set, f_wrote, f_way, f_vals)


def _check_bit_exact(rng, b, bm, s=16, w=4, v=3, **batch_kw):
    import jax.numpy as jnp

    key_hi, key_lo, stamp, epoch, value = _rand_state(rng, s, w, v)
    (h_hi, h_lo, set_idx, admit, static_hit, epochs, minep,
     f_set, f_wrote, f_way, f_vals) = _rand_batch(rng, b, s, v, **batch_kw)
    clock = 100
    ref = serve_fused_ref(
        key_hi.copy(), key_lo.copy(), stamp.copy(), value.copy(),
        h_hi, h_lo, set_idx, admit, static_hit, clock,
        epoch=epoch.copy(), epochs=epochs, min_epoch=minep,
        f_set_idx=f_set, f_wrote=f_wrote, f_way=f_way, f_values=f_vals,
    )
    ks = jnp.asarray(pack_words(key_hi, key_lo, stamp, epoch))
    for use_kernel in (False, True):
        out = serve_fused_op(
            ks, jnp.asarray(value),
            jnp.asarray(h_hi), jnp.asarray(h_lo), jnp.asarray(set_idx),
            jnp.asarray(admit), jnp.asarray(static_hit),
            jnp.asarray(clock, jnp.int32),
            f_set_idx=jnp.asarray(f_set), f_wrote=jnp.asarray(f_wrote),
            f_way=jnp.asarray(f_way), f_values=jnp.asarray(f_vals),
            epochs=jnp.asarray(epochs), min_epoch=jnp.asarray(minep),
            use_kernel=use_kernel, interpret=True, bm=bm,
        )
        o_hi, o_lo, o_st = unpack_words(np.asarray(out["ks"]))
        o_ep = unpack_epoch(np.asarray(out["ks"]))
        tag = f"use_kernel={use_kernel} bm={bm} b={b}"
        assert np.array_equal(o_hi, ref["key_hi"]), tag
        assert np.array_equal(o_lo, ref["key_lo"]), tag
        assert np.array_equal(o_st, ref["stamp"]), tag
        assert np.array_equal(o_ep, ref["epoch"]), tag
        assert np.array_equal(np.asarray(out["value"]), ref["value"]), tag
        assert np.array_equal(np.asarray(out["values"]), ref["values"]), tag
        for k in ("pre_hit", "pre_way", "pre_stale", "pre_epoch", "wrote", "way"):
            assert np.array_equal(np.asarray(out[k]), ref[k]), (tag, k)


@pytest.mark.parametrize(
    "b,bm",
    [
        (37, 8),   # ragged final tile (37 pads to 40, last tile part-pad)
        (8, 8),    # exactly one tile
        (3, 8),    # batch smaller than the tile
        (65, 16),  # ragged with a larger tile
    ],
)
def test_serve_kernel_bit_exact_ragged_tiles(b, bm):
    _check_bit_exact(np.random.default_rng(b * 31 + bm), b, bm)


def test_serve_kernel_all_pad_batch_is_inert():
    rng = np.random.default_rng(17)
    _check_bit_exact(rng, 24, 8, pad_frac=1.0, static_frac=0.0, dup=False)


def test_serve_kernel_all_static_hit_batch():
    rng = np.random.default_rng(19)
    _check_bit_exact(rng, 24, 8, pad_frac=0.0, static_frac=1.0)


def test_serve_kernel_duplicate_key_batches():
    # every request the same key: maximal in-set conflict chains
    import jax.numpy as jnp

    rng = np.random.default_rng(23)
    s, w, v, b = 8, 4, 3, 32
    key_hi, key_lo, stamp, epoch, value = _rand_state(rng, s, w, v)
    h64 = np.full(b, splitmix64(np.array([7]))[0], np.uint64)
    h_hi, h_lo = pack_hashes(h64)
    set_idx = np.full(b, 3, np.int32)
    admit = np.ones(b, bool)
    static_hit = np.zeros(b, bool)
    clock = 5
    ref = serve_fused_ref(
        key_hi.copy(), key_lo.copy(), stamp.copy(), value.copy(),
        h_hi, h_lo, set_idx, admit, static_hit, clock, epoch=epoch.copy(),
    )
    ks = jnp.asarray(pack_words(key_hi, key_lo, stamp, epoch))
    for use_kernel in (False, True):
        out = serve_fused_op(
            ks, jnp.asarray(value), jnp.asarray(h_hi), jnp.asarray(h_lo),
            jnp.asarray(set_idx), jnp.asarray(admit), jnp.asarray(static_hit),
            jnp.asarray(clock, jnp.int32), use_kernel=use_kernel,
            interpret=True, bm=8,
        )
        o_hi, o_lo, o_st = unpack_words(np.asarray(out["ks"]))
        assert np.array_equal(o_hi, ref["key_hi"]), use_kernel
        assert np.array_equal(o_lo, ref["key_lo"]), use_kernel
        assert np.array_equal(o_st, ref["stamp"]), use_kernel
        assert np.array_equal(np.asarray(out["values"]), ref["values"])
        assert np.array_equal(np.asarray(out["wrote"]), ref["wrote"])


def test_fill_winner_slots_last_writer_wins_and_drops_oob():
    import jax.numpy as jnp

    nslots, w = 8, 2
    f_set = jnp.asarray([0, 0, 1, 9, 2], jnp.int32)
    f_way = jnp.asarray([1, 1, 0, 0, 1], jnp.int32)
    f_wrote = jnp.asarray([True, True, False, True, True])
    slots = np.asarray(fill_winner_slots(nslots, w, f_set, f_wrote, f_way))
    # entry 0 loses slot 1 to entry 1 (later writer); entry 2 didn't
    # write; entry 3 is out of bounds; entry 4 wins slot 5
    assert slots.tolist() == [nslots, 1, nslots, nslots, 5]


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(
        b=st.integers(1, 48),
        bm=st.sampled_from([8, 16]),
        seed=st.integers(0, 2**16),
        pad_frac=st.sampled_from([0.0, 0.2, 1.0]),
    )
    def test_serve_kernel_bit_exact_property(b, bm, seed, pad_frac):
        _check_bit_exact(
            np.random.default_rng(seed), b, bm, pad_frac=pad_frac
        )


# -- autotune table ----------------------------------------------------------


def test_autotune_round_trip_and_fallback(tmp_path, monkeypatch):
    path = str(tmp_path / "tune.json")
    monkeypatch.setenv(autotune.ENV_PATH, path)
    autotune.clear_cache()
    assert autotune.table_path() == path
    assert autotune.load_table() is None  # absent -> None, memoized
    assert autotune.best_bm("cpu", 4096) == autotune.DEFAULT_BM
    autotune.save_table({
        "entries": {
            "cpu/256": {"bm": 32, "us_per_call": 10.0},
            "cpu/4096": {"bm": 128, "us_per_call": 99.0},
            "tpu/4096": {"bm": 512, "us_per_call": 5.0},
        },
    })
    assert autotune.load_table()["schema"] == autotune.AUTOTUNE_SCHEMA
    assert autotune.best_bm("cpu", 4096) == 128  # exact
    assert autotune.best_bm("cpu", 64) == 32  # nearest larger bucket
    assert autotune.best_bm("cpu", 1024) == 128  # between entries -> larger
    assert autotune.best_bm("cpu", 8192) == autotune.DEFAULT_BM  # none larger
    assert autotune.best_bm("gpu", 4096) == autotune.DEFAULT_BM  # backend miss
    assert autotune.best_bm("tpu", 4096) == 512


def test_autotune_corrupt_table_falls_back(tmp_path, monkeypatch):
    path = str(tmp_path / "tune.json")
    monkeypatch.setenv(autotune.ENV_PATH, path)
    autotune.clear_cache()
    with open(path, "w") as f:
        f.write("{not json")
    assert autotune.load_table() is None
    assert autotune.best_bm("cpu", 256) == autotune.DEFAULT_BM
    autotune.clear_cache()
    with open(path, "w") as f:
        f.write('{"schema": 99, "entries": {}}')  # wrong schema version
    assert autotune.load_table() is None
    autotune.clear_cache()


def test_autotune_ignores_a_stray_table_in_the_working_directory(
    tmp_path, monkeypatch
):
    monkeypatch.chdir(tmp_path)
    monkeypatch.delenv(autotune.ENV_PATH, raising=False)
    autotune.clear_cache()
    backend = jax.default_backend()
    (tmp_path / autotune.DEFAULT_PATH).write_text(json.dumps({
        "schema": autotune.AUTOTUNE_SCHEMA,
        "entries": {f"{backend}/256": {"bm": 64}},
    }))
    try:
        assert autotune.table_path() is None
        assert autotune.best_bm(backend, 256) == autotune.DEFAULT_BM
    finally:
        autotune.clear_cache()


def test_broker_picks_up_autotuned_bm(tmp_path, monkeypatch):
    path = str(tmp_path / "tune.json")
    monkeypatch.setenv(autotune.ENV_PATH, path)
    autotune.clear_cache()
    backend = jax.default_backend()
    autotune.save_table({"entries": {f"{backend}/256": {"bm": 64}}})
    broker = _make_broker("device", BucketSpec(min_size=8))
    try:
        assert broker._bm == 64  # microbatch 256 -> bucket 256 -> tuned bm
    finally:
        broker.close()
        autotune.clear_cache()
