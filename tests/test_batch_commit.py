"""Vectorized / Pallas batch commit vs the sequential fori_loop oracle.

The sequential `STDDeviceCache.commit` is the reference semantics; the
conflict-aware vectorized commit and the fused Pallas kernel (interpret
mode on CPU) must reproduce its final state bit-for-bit -- including
stamps and the deferred value fill -- under forced set conflicts,
duplicate keys, mixed admission and static hits.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import NO_TOPIC, LRUCache, STDCache
from repro.kernels.cache_ops import (
    PAD_HI,
    PAD_LO,
    pack_words,
    probe_and_commit_op,
    replay_lane_rounds,
    replay_schedule,
    serve_fused_op,
    unpack_words,
)
from repro.kernels.cache_ops.ref import probe_and_commit_ref, serve_fused_ref
from repro.serving import (
    Broker,
    DeviceCacheConfig,
    STDDeviceCache,
    pack_hashes,
    splitmix64,
    unpack_state,
)

STATE_KEYS = ("ks", "value", "clock")


def _cache(n_sets_scale=1, ways=4, value_dim=2, static=(3, 4)):
    cfg = DeviceCacheConfig(
        total_entries=64 * n_sets_scale,
        ways=ways,
        value_dim=value_dim,
        topic_entries={0: 16 * n_sets_scale, 1: 16 * n_sets_scale},
        dynamic_entries=32 * n_sets_scale,
    )
    return STDDeviceCache(
        cfg,
        static_hashes=splitmix64(np.array(static)) if static else None,
        static_values=np.ones((len(static), value_dim), np.int32) if static else None,
    )


def _batch(cache, rng, qids, admit_p=0.7):
    b = len(qids)
    topics = rng.integers(-1, 2, size=b)
    parts = jnp.asarray(cache.parts_for(topics))
    hi, lo = pack_hashes(splitmix64(np.asarray(qids)))
    vals = jnp.asarray(rng.integers(0, 1000, size=(b, cache.cfg.value_dim)), jnp.int32)
    admit = jnp.asarray(rng.random(b) < admit_p)
    return jnp.asarray(hi), jnp.asarray(lo), parts, vals, admit


def _assert_states_equal(ref, got, label):
    for k in STATE_KEYS:
        a, b = np.asarray(ref[k]), np.asarray(got[k])
        assert (a == b).all(), f"{label}: state[{k}] diverged at {np.argwhere(a != b)[:5]}"


def _drive_all_paths(cache, state, batches):
    """Chain batches through oracle / vectorized / kernel / host engines."""
    for i, (hi, lo, parts, vals, admit) in enumerate(batches):
        s_seq = cache.commit(state, hi, lo, parts, vals, admit)
        s_vec = cache.commit_vectorized(state, hi, lo, parts, vals, admit)
        s_ker = cache.commit_vectorized(
            state, hi, lo, parts, vals, admit, use_kernel=True, interpret=True
        )
        s_host = cache.commit_host(state, hi, lo, np.asarray(parts), vals, admit)
        _assert_states_equal(s_seq, s_vec, f"batch{i}/vectorized")
        _assert_states_equal(s_seq, s_ker, f"batch{i}/pallas")
        _assert_states_equal(s_seq, s_host, f"batch{i}/host")
        # fused probe-and-commit: probe parity + deferred fill parity
        hit0, lay0, val0, stale0 = cache.probe(state, hi, lo, parts)
        assert not np.asarray(stale0).any()  # no min_epoch: nothing expires
        for label, fused, fill in (
            ("fused", cache.probe_and_commit, cache.fill_values),
            ("fused_host", cache.probe_and_commit_host, cache.fill_values_host),
        ):
            hit1, lay1, val1, stale1, s_fused, (set_idx, wrote, way) = fused(
                state, hi, lo, np.asarray(parts) if "host" in label else parts, admit
            )
            assert (np.asarray(hit0) == np.asarray(hit1)).all(), label
            assert (np.asarray(lay0) == np.asarray(lay1)).all(), label
            assert (np.asarray(val0) == np.asarray(val1)).all(), label
            assert not np.asarray(stale1).any(), label
            s_fused = fill(s_fused, set_idx, wrote, way, vals)
            _assert_states_equal(s_seq, s_fused, f"batch{i}/{label}")
        state = s_seq
    return state


if HAVE_HYPOTHESIS:
    _cases = given(st.integers(0, 10_000))
    _settings = settings(max_examples=8, deadline=None)
else:
    def _cases(f):
        return pytest.mark.parametrize("seed", [0, 1, 7, 13, 42])(f)

    def _settings(f):
        return f


@_settings
@_cases
def test_random_batches_all_paths_bit_exact(seed):
    rng = np.random.default_rng(seed)
    cache = _cache()
    batches = [
        _batch(cache, rng, rng.integers(0, 60, size=int(rng.integers(1, 96))))
        for _ in range(3)
    ]
    _drive_all_paths(cache, dict(cache.init_state), batches)


@pytest.mark.parametrize("seed", [0, 3])
def test_adversarial_same_set_and_duplicates(seed):
    """Worst-case conflict depth: every request lands in one set."""
    rng = np.random.default_rng(seed)
    ways = 4
    cfg = DeviceCacheConfig(
        total_entries=ways, ways=ways, value_dim=1, topic_entries={}, dynamic_entries=ways
    )
    cache = STDDeviceCache(cfg)
    batches = []
    # all-same-set with duplicate keys: one set, 48 sequential conflicts
    batches.append(_batch(cache, rng, rng.integers(0, 6, size=48), admit_p=0.6))
    # all duplicates of a single key, alternating admission
    batches.append(_batch(cache, rng, np.full(32, 9), admit_p=0.5))
    # every key distinct, all admitted: pure eviction churn
    batches.append(_batch(cache, rng, rng.permutation(100)[:40], admit_p=1.0))
    # depth past HOST_DEPTH_LIMIT: the host engines dispatch to the
    # compiled sequential replay and must stay bit-exact
    assert 150 > STDDeviceCache.HOST_DEPTH_LIMIT
    batches.append(_batch(cache, rng, rng.integers(0, 20, size=150), admit_p=0.7))
    _drive_all_paths(cache, dict(cache.init_state), batches)


def test_static_hits_never_write():
    rng = np.random.default_rng(5)
    cache = _cache(static=(3, 4, 5, 6))
    qids = np.array([3, 4, 5, 6, 3, 4] * 4)
    batches = [_batch(cache, rng, qids, admit_p=1.0)]
    state = _drive_all_paths(cache, dict(cache.init_state), batches)
    key_hi, _, _ = unpack_state({"ks": np.asarray(state["ks"])})
    assert (key_hi == 0).all(), "static hits must not insert"


def test_kernel_matches_numpy_ref_per_request_outputs():
    """The Pallas kernel's per-request write plan equals the numpy oracle's."""
    rng = np.random.default_rng(11)
    cache = _cache()
    state = dict(cache.init_state)
    for i in range(3):
        hi, lo, parts, vals, admit = _batch(cache, rng, rng.integers(0, 50, size=64))
        static_hit, _ = cache.static_lookup(state, hi, lo)
        set_idx = cache._set_index(lo, parts)
        key_hi, key_lo, stamp = unpack_words(np.asarray(state["ks"]))
        ref = probe_and_commit_ref(
            key_hi, key_lo, stamp,
            np.asarray(hi), np.asarray(lo), np.asarray(set_idx),
            np.asarray(admit), np.asarray(static_hit), int(state["clock"]),
        )
        ref_ks = pack_words(ref["key_hi"], ref["key_lo"], ref["stamp"])
        for use_kernel in (False, True):
            got = probe_and_commit_op(
                state["ks"], hi, lo, set_idx, admit, static_hit, state["clock"],
                use_kernel=use_kernel, interpret=True,
            )
            assert (np.asarray(got["ks"]) == ref_ks).all(), (i, use_kernel, "ks")
            for k in ("pre_hit", "pre_way", "wrote", "way"):
                assert (np.asarray(got[k]) == np.asarray(ref[k])).all(), (i, use_kernel, k)
        state = cache.commit(state, hi, lo, parts, vals, admit)


def test_empty_batch_is_identity():
    cache = _cache()
    state = dict(cache.init_state)
    z = jnp.zeros((0,), jnp.uint32)
    out = cache.commit_vectorized(
        state, z, z, jnp.zeros((0,), jnp.int32),
        jnp.zeros((0, cache.cfg.value_dim), jnp.int32), jnp.zeros((0,), bool),
    )
    _assert_states_equal(state, out, "empty")


@pytest.mark.parametrize(
    "topic_entries",
    [
        {3: 16, 7: 16, 11: 0, 40: 16},
        # id span past the dense-LUT cutoff: per-topic loop fallback
        {3: 16, 7: 16, 5_000_000: 16},
    ],
)
def test_parts_for_lookup_matches_mapping(topic_entries):
    """Dense LUT and sparse-id fallback both equal the per-topic definition."""
    cfg = DeviceCacheConfig(
        total_entries=80, ways=4, value_dim=1,
        topic_entries=topic_entries, dynamic_entries=32,
    )
    cache = STDDeviceCache(cfg)
    topics = np.array([-5, -1, 0, 3, 7, 11, 12, 40, 41, 1000, 5_000_000])
    got = cache.parts_for(topics)
    for t, p in zip(topics, got):
        expect = cache.part_of_topic.get(int(t), cache.k)
        if expect != cache.k and cache.part_sets[expect] == 0:
            expect = cache.k  # zero-set partitions fall through to dynamic
        assert p == expect, (t, p, expect)


def test_broker_serves_empty_batch():
    cfg = DeviceCacheConfig(
        total_entries=16, ways=4, value_dim=1, topic_entries={}, dynamic_entries=16
    )
    broker = Broker(
        STDDeviceCache(cfg),
        [lambda q: q[:, None].astype(np.int32)],
        topic_of=lambda q: np.full(len(q), -1),
    )
    values, hit = broker.serve(np.zeros(0, np.int64))
    assert values.shape[0] == 0 and hit.shape[0] == 0
    assert broker.stats.requests == 0


@pytest.mark.parametrize(
    "use_kernel,engine,n_req",
    [(False, "auto", 400), (False, "device", 200), (True, "device", 96)],
)
def test_broker_batch1_matches_exact_simulator(use_kernel, engine, n_req):
    """Fused batch-1 serving == the paper's exact STDCache, per request."""
    rng = np.random.default_rng(2)
    ways = 4
    # one set per partition: each section is then exactly a W-entry LRU
    cfg = DeviceCacheConfig(
        total_entries=4 * ways, ways=ways, value_dim=1,
        topic_entries={0: ways, 1: ways, 2: ways}, dynamic_entries=ways,
    )
    static_q = np.array([0, 1])
    topic_of_q = rng.integers(-1, 3, size=200)
    topic_of_q[static_q] = NO_TOPIC
    cache = STDDeviceCache(
        cfg,
        static_hashes=splitmix64(static_q),
        static_values=static_q[:, None].astype(np.int32),
    )

    def backend(qids):
        return qids[:, None].astype(np.int32)

    broker = Broker(
        cache, [backend], lambda q: topic_of_q[q], use_kernel=use_kernel, engine=engine
    )
    sim = STDCache(
        static_keys=[int(q) for q in static_q],
        sections={t: LRUCache(ways) for t in range(3)},
        dynamic_capacity=ways,
        topic_of=lambda k: int(topic_of_q[k]),
    )
    stream = rng.integers(0, 200, size=n_req)
    for i, q in enumerate(stream):
        values, hit = broker.serve(np.array([q]))
        expect = sim.request_ex(int(q))
        assert bool(hit[0]) == expect.hit, f"request {i} (key {q}) diverged"
        assert values[0, 0] == q
    assert broker.stats.hits > 0 and broker.stats.hits < broker.stats.requests


# -- the tiered conflict replay ----------------------------------------------

_W, _V = 8, 3


def _segments(case, b):
    """Segment lengths (one set each) of a crafted batch; the rest of the
    batch lands in sets of its own."""
    if case == "one_set":
        return [b]
    if case == "distinct":
        return []
    if case == "tier_edges":
        # lengths at every tier edge 2^t - 2, 2^t - 1, 2^t, and past the
        # 128-lane floor, as far as the batch holds them
        want = [n for t in range(1, 9) for n in ((1 << t) - 2, (1 << t) - 1, 1 << t)]
        lens = []
        for n in sorted({n for n in want + [129, 200] if n > 0}, reverse=True):
            if sum(lens) + n <= b:
                lens.append(n)
        return lens
    return [max(b // 2, 1)]  # one deep segment (hot key, static, fresh)


def _crafted(case, b, seed):
    """State and one batch of ``b`` for an exactness case (numpy)."""
    rng = np.random.default_rng(seed)
    lens = _segments(case, b)
    n_sets = len(lens) + (b - sum(lens)) + 3
    sets = rng.permutation(n_sets)
    set_idx = np.concatenate(
        [np.full(n, sets[i]) for i, n in enumerate(lens)]
        + [sets[len(lens) : len(lens) + b - sum(lens)]]
    ).astype(np.int32)
    # a small key pool per set, so that rounds hit, miss and evict
    pool = rng.integers(1, 2**31, size=(n_sets, 12)).astype(np.uint32)
    pick = rng.integers(0, 12, size=b)
    h_hi = pool[set_idx, pick]
    h_lo = (h_hi * np.uint32(2654435761)) | np.uint32(1)
    admit = rng.random(b) < 0.7
    static = np.zeros(b, bool)
    deep = set_idx == set_idx[0]
    if case in ("hot_key_admit", "hot_key_no_admit"):
        h_hi[deep], h_lo[deep] = h_hi[0], h_lo[0]
        admit[deep] = case == "hot_key_admit"
    if case == "static_and_pads":
        static = deep & (rng.random(b) < 0.3)
        pads = deep & ~static & (rng.random(b) < 0.25)
        h_hi[pads], h_lo[pads] = PAD_HI, PAD_LO
    order = rng.permutation(b)  # segments interleave in arrival order
    req = [x[order] for x in (h_hi, h_lo, set_idx, admit, static)]
    occ = rng.random((n_sets, _W)) < 0.6
    key_hi = np.where(occ, pool[np.arange(n_sets)[:, None], rng.integers(0, 12, (n_sets, _W))], 0)
    state = dict(
        key_hi=key_hi.astype(np.uint32),
        key_lo=np.where(occ, (key_hi * np.uint32(2654435761)) | np.uint32(1), 0).astype(np.uint32),
        stamp=rng.integers(0, 1000, (n_sets, _W)).astype(np.int32),
        epoch=rng.integers(0, 6, (n_sets, _W)).astype(np.uint32),
        value=rng.integers(0, 100, (n_sets, _W, _V)).astype(np.int32),
    )
    epochs = rng.integers(0, 9, b).astype(np.uint32)
    min_epoch = (rng.integers(0, 5, b) if case == "fresh" else np.zeros(b)).astype(np.uint32)
    fill = (
        rng.integers(0, n_sets, b).astype(np.int32), rng.random(b) < 0.3,
        rng.integers(0, _W, b).astype(np.int32), rng.integers(0, 100, (b, _V)).astype(np.int32),
    )
    return state, req, epochs, min_epoch, fill


@pytest.mark.parametrize("b", [1, 7, 128, 129, 200, 256, 1000, 4096])
def test_replay_schedule_covers_every_round_wide_enough(b):
    """Tiers cover rounds [0, b) in order, each at least as wide as the
    segments that can still be active (``b // (round + 1)``), narrowing
    to the 128-lane floor; at 4096 the lane-rounds are those of the
    design (deepest segment 1, 3, 7, 19, 31, 65, 100)."""
    tiers = replay_schedule(b)
    assert tiers[0][0] == 0 and tiers[-1][1] == b
    assert all(a[1] == c[0] for a, c in zip(tiers, tiers[1:]))
    for start, stop, width in tiers:
        assert start < stop and b // (start + 1) <= width <= b
    assert tiers[-1][2] == min(b, 128)
    for depth in {1, min(3, b), b}:
        assert replay_lane_rounds(b, depth) <= b * depth
        if b <= 128:
            assert replay_lane_rounds(b, depth) == b * depth
    if b == 4096:
        assert [replay_lane_rounds(b, d) for d in (1, 3, 7, 19, 31, 65, 100)] == [
            4096, 8192, 12288, 17408, 20480, 24832, 29312,
        ]


_jit_probe = jax.jit(probe_and_commit_op, static_argnames=("use_kernel", "interpret"))
_jit_serve = jax.jit(serve_fused_op, static_argnames=("use_kernel", "interpret"))

_CASES = (
    "one_set", "tier_edges", "distinct", "hot_key_admit", "hot_key_no_admit",
    "static_and_pads", "fresh",
)


@pytest.mark.parametrize("b", [1, 7, 128, 129, 256, 4096])
@pytest.mark.parametrize("case", _CASES)
def test_tiered_replay_is_bit_exact(case, b):
    """The jnp engine's tiered replay (probe/commit and the one-call
    serve) equals the sequential oracles and the Pallas interpret path."""
    state, (h_hi, h_lo, set_idx, admit, static), epochs, min_epoch, fill = _crafted(
        case, b, seed=b
    )
    ks = jnp.asarray(pack_words(state["key_hi"], state["key_lo"], state["stamp"], state["epoch"]))
    clock = jnp.int32(5000)
    req = [jnp.asarray(x) for x in (h_hi, h_lo, set_idx, admit, static)]
    fresh = dict(epochs=jnp.asarray(epochs), min_epoch=jnp.asarray(min_epoch))
    ref = probe_and_commit_ref(
        state["key_hi"], state["key_lo"], state["stamp"], h_hi, h_lo, set_idx,
        admit, static, 5000, epoch=state["epoch"], epochs=epochs, min_epoch=min_epoch,
    )
    ref_ks = pack_words(ref["key_hi"], ref["key_lo"], ref["stamp"], ref["epoch"])
    sref = serve_fused_ref(
        state["key_hi"], state["key_lo"], state["stamp"], state["value"], h_hi, h_lo,
        set_idx, admit, static, 5000, epoch=state["epoch"], epochs=epochs,
        min_epoch=min_epoch, f_set_idx=fill[0], f_wrote=fill[1], f_way=fill[2],
        f_values=fill[3],
    )
    keys = ("pre_hit", "pre_way", "pre_stale", "pre_epoch", "wrote", "way")
    for use_kernel in (False, True):
        label = f"{case}/B={b}/{'pallas' if use_kernel else 'jnp'}"
        got = _jit_probe(ks, *req, clock, **fresh, use_kernel=use_kernel, interpret=True)
        assert (np.asarray(got["ks"]) == ref_ks).all(), (label, "ks")
        for k in keys:
            assert (np.asarray(got[k]) == ref[k]).all(), (label, k)
        got = _jit_serve(
            ks, jnp.asarray(state["value"]), *req, clock,
            *(jnp.asarray(x) for x in fill), **fresh, use_kernel=use_kernel, interpret=True,
        )
        assert (np.asarray(got["ks"]) == ref_ks).all(), (label, "serve ks")
        for k in keys + ("value", "values"):
            assert (np.asarray(got[k]) == sref[k]).all(), (label, "serve", k)
