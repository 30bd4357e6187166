"""Device-resident STD cache: the paper's data structure, TPU-native.

The CPU hash-table LRU of the paper becomes three dense arrays -- a W-way
set-associative cache whose *address space is partitioned by topic*:

    ks    : (S, 4W) uint32  packed per-slot words: columns [0:W] key_hi,
                            [W:2W] key_lo, [2W:3W] recency stamp
                            (int32 bit-cast), [3W:4W] insertion epoch;
                            key 0 = empty slot
    value : (S, W, V) int32 cached result payload (doc ids)

The packed key/stamp/epoch layout makes the hot path one gather (probe)
and one scatter (commit) over a lane-friendly (S, 4W) array instead of
four of each over (S, W) strips; ``pack_words`` / ``unpack_words`` are
exact bit-reinterpretations, so the fori_loop oracle keeps operating on
the unpacked (key_hi, key_lo, stamp) view.  The epoch word carries the
freshness subsystem (docs/freshness.md): every update op takes optional
``epochs`` (insertion epoch stamped on writes) and ``min_epoch`` (the
per-request freshness floor; a match below it is a *stale* hit that
schedules a value refresh).  Both default to zero, which makes expiry
provably inert -- the pre-freshness semantics bit-for-bit.

Topic tau owns the contiguous set range [offset[tau], offset[tau]+sets[tau])
sized by the paper's proportional allocation; the dynamic cache is
partition k; the static cache is a sorted hash array probed by vectorized
lexicographic binary search (read-only, refreshed offline).

One key is *reserved*: ``PAD_KEY`` (query id -1, packed hash
``(PAD_HI, PAD_LO)``).  It is never admitted, never hits, and never
displaces a resident entry, in every engine -- the invariant that lets
shape-bucketed callers pad ragged batches up to a fixed set of lengths
so the jitted serving path compiles O(#buckets) shapes instead of one
per distinct batch length (see docs/serving.md).  ``splitmix64`` maps
``PAD_KEY`` to the pad hash and never hashes a real key to it (or to 0,
the empty-slot sentinel).

Probes are fully parallel (gather + compare).  Updates come in two
flavors: `commit` serializes within a batch via `lax.fori_loop` (the
reference semantics, kept as the oracle), and `commit_vectorized` /
`probe_and_commit` resolve within-batch set conflicts with a sort +
segmented replay whose sequential depth is the deepest set conflict, not
the batch size (see repro.kernels.cache_ops) -- bit-exact with the
oracle, property-tested.  Because partitions are independent, sharding
the set axis across devices creates zero cross-device traffic beyond
routing -- the paper's own design choice is what makes the cache scale
out.  See docs/device_cache.md.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.alloc import proportional_allocation
from ..core.spec import PAD_KEY
from ..kernels.cache_ops.kernel import PAD_HI as _PAD_HI_INT
from ..kernels.cache_ops.kernel import PAD_LO as _PAD_LO_INT
from ..kernels.cache_ops.ops import (
    pack_words,
    probe_and_commit_op,
    serve_fused_op,
    unpack_epoch,
    unpack_words,
)

DYNAMIC = -1  # callers pass topic=-1 for no-topic queries

#: the reserved pad key's packed hash words (host-side numpy mirrors of
#: the kernel-layer constants; they must agree, asserted below)
PAD_HI = np.uint32(_PAD_HI_INT)
PAD_LO = np.uint32(_PAD_LO_INT)
#: the reserved pad key's 64-bit hash -- splitmix64(PAD_KEY) lands here
#: and no real key ever does
PAD_H64 = (np.uint64(PAD_HI) << np.uint64(32)) | np.uint64(PAD_LO)
assert int(np.uint64(np.int64(PAD_KEY))) == int(PAD_H64), "PAD_KEY/PAD_H64 drift"


def splitmix64(x: np.ndarray) -> np.ndarray:
    """Deterministic 64-bit mix of query ids (host side, numpy uint64).

    Two hash values are reserved and never produced for a real key: 0 is
    the empty-slot sentinel and ``PAD_H64`` is the shape-padding
    sentinel; the astronomically unlikely real key that mixes onto one of
    them is deterministically remapped.  The reserved query id
    ``PAD_KEY`` (= -1) maps *exactly* to ``PAD_H64``.
    """
    x64 = np.asarray(x)
    if x64.dtype != np.uint64:
        # int -> uint64 via astype (C wrap): PAD_KEY == -1 becomes all-ones
        x64 = x64.astype(np.int64, copy=False).astype(np.uint64)
    is_pad = x64 == PAD_H64
    z = x64 + np.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    z = z ^ (z >> np.uint64(31))
    z[z == 0] = 1  # 0 is the empty-slot sentinel
    z[z == PAD_H64] = PAD_H64 ^ np.uint64(1)  # the pad hash is reserved
    z[is_pad] = PAD_H64
    return z


def pack_hashes(h64: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    return (h64 >> np.uint64(32)).astype(np.uint32), (h64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def unpack_state(state) -> Tuple[Any, Any, Any]:
    """The unpacked (key_hi, key_lo, stamp) view of a cache state's packed
    ``ks`` array -- numpy views (writable) for host states, jnp slices for
    device states."""
    return unpack_words(state["ks"])


def pad_batch(h_hi, h_lo, parts, pad_part: int, bp: int, values=None, admit=None):
    """Extend a request batch to ``bp`` entries with the reserved pad key.

    The single place the pad convention lives: pads carry the packed pad
    hash, route to ``pad_part`` (the partition only picks which set an
    inert probe touches), zero values and ``admit=False``.  ``values`` /
    ``admit`` pass through untouched when None.  Returns
    ``(h_hi, h_lo, parts, values, admit)``; a no-op when ``bp <= len``.
    """
    n = len(h_hi)
    if bp > n:
        p = bp - n
        h_hi = np.concatenate([h_hi, np.full(p, PAD_HI, np.uint32)])
        h_lo = np.concatenate([h_lo, np.full(p, PAD_LO, np.uint32)])
        parts = np.concatenate(
            [np.asarray(parts, np.int32), np.full(p, pad_part, np.int32)]
        )
        if values is not None:
            values = np.asarray(values, np.int32)
            values = np.concatenate(
                [values, np.zeros((p, values.shape[1]), np.int32)]
            )
        if admit is not None:
            admit = np.concatenate([np.asarray(admit, bool), np.zeros(p, bool)])
    return h_hi, h_lo, parts, values, admit


def _sequential_replay(
    key_hi, key_lo, stamp, epoch, h_hi, h_lo, set_idx, admit, static_hit,
    clock, epochs, min_epoch,
):
    """The oracle commit's fori_loop, additionally emitting the per-request
    write plan (wrote, way) the deferred value fill needs.  Fallback engine
    for conflict depths where round-based replay degenerates.  ``wrote``
    covers inserts *and* stale refreshes (hits whose resident epoch is
    below the request's ``min_epoch`` floor)."""
    b = h_hi.shape[0]
    pad = (h_hi == PAD_HI) & (h_lo == PAD_LO)
    # effective write epoch (mirrors probe_and_commit_op): a pristine
    # fresh hit keeps its resident epoch, so a mid-batch evict +
    # re-insert of the same key (served and re-filled with its probed,
    # unchanged value) cannot launder the entry's age; idempotent, so
    # callers that already applied the rule compose safely
    sc0 = jnp.minimum(set_idx, key_hi.shape[0] - 1)
    p_hi, p_lo = key_hi[sc0], key_lo[sc0]
    pm0 = (p_hi == h_hi[:, None]) & (p_lo == h_lo[:, None]) & (p_hi != 0)
    pm0 = pm0 & ~pad[:, None]
    pm0_ep = jnp.where(pm0, epoch[sc0], 0).max(axis=1)
    epochs = jnp.where(pm0.any(axis=1) & (pm0_ep >= min_epoch), pm0_ep, epochs)

    def body(i, st):
        key_hi, key_lo, stamp, epoch, wrote, way_out = st
        s = set_idx[i]
        row_hi = key_hi[s]
        row_lo = key_lo[s]
        match = (row_hi == h_hi[i]) & (row_lo == h_lo[i]) & (row_hi != 0) & ~pad[i]
        is_hit = match.any()
        way = jnp.where(match.any(), jnp.argmax(match), jnp.argmin(stamp[s]))
        stale = is_hit & (epoch[s, way] < min_epoch[i])
        do_write = (~static_hit[i]) & ~pad[i] & (is_hit | admit[i])
        refresh = do_write & (~is_hit | stale)
        key_hi = key_hi.at[s, way].set(jnp.where(do_write, h_hi[i], key_hi[s, way]))
        key_lo = key_lo.at[s, way].set(jnp.where(do_write, h_lo[i], key_lo[s, way]))
        stamp = stamp.at[s, way].set(jnp.where(do_write, clock + 1 + i, stamp[s, way]))
        epoch = epoch.at[s, way].set(jnp.where(refresh, epochs[i], epoch[s, way]))
        wrote = wrote.at[i].set(refresh)
        way_out = way_out.at[i].set(way.astype(jnp.int32))
        return key_hi, key_lo, stamp, epoch, wrote, way_out

    return jax.lax.fori_loop(
        0, b, body,
        (key_hi, key_lo, stamp, epoch, jnp.zeros(b, bool), jnp.zeros(b, jnp.int32)),
    )


@dataclasses.dataclass(frozen=True)
class DeviceCacheConfig:
    total_entries: int
    ways: int = 8
    value_dim: int = 8
    #: per-topic entry counts (proportional allocation); dynamic entries
    #: are whatever remains
    topic_entries: Mapping[int, int] = dataclasses.field(default_factory=dict)
    dynamic_entries: int = 0
    static_entries: int = 0

    #: the reserved never-resident pad key (query-id level; its packed
    #: hash is ``(PAD_HI, PAD_LO)``) -- part of the static-shape serving
    #: contract every engine honours
    @property
    def pad_key(self) -> int:
        return PAD_KEY

    @classmethod
    def build(
        cls,
        n: int,
        f_s: float,
        f_t: float,
        topic_distinct: Mapping[int, int],
        ways: int = 8,
        value_dim: int = 8,
    ) -> "DeviceCacheConfig":
        n_s = int(round(f_s * n))
        n_t = int(round(f_t * n))
        n_d = n - n_s - n_t
        sizes = proportional_allocation(n_t, topic_distinct, exact=True)
        return cls(
            total_entries=n,
            ways=ways,
            value_dim=value_dim,
            topic_entries=sizes,
            dynamic_entries=n_d,
            static_entries=n_s,
        )

    @classmethod
    def from_spec(
        cls,
        spec,
        topic_distinct: Mapping[int, int],
        ways: int = 8,
        value_dim: int = 8,
    ) -> "DeviceCacheConfig":
        """Compile a :class:`repro.core.spec.CacheSpec` to a device config."""
        return spec.to_device(topic_distinct, ways=ways, value_dim=value_dim)

    @property
    def topic_budget(self) -> int:
        """Total entries owned by the topic layer (invariant under rebalance)."""
        return int(sum(self.topic_entries.values()))

    def rebalanced(self, popularity: Mapping[int, float]) -> "DeviceCacheConfig":
        """Same layer budgets, topic entries re-split by live popularity.

        The static/dynamic layers and the topic layer's *total* budget are
        untouched; only the per-topic split moves (paper Sec. 3.3
        proportional allocation, fed tracked counts instead of training
        distinct counts).  The topic universe is this config's -- topics
        missing from ``popularity`` weigh 0.
        """
        weights = {
            int(t): float(popularity.get(int(t), 0.0)) for t in self.topic_entries
        }
        sizes = proportional_allocation(self.topic_budget, weights, exact=True)
        return dataclasses.replace(self, topic_entries=sizes)

    # -- serialization (checkpoints embed the live allocation) --------------

    def to_json(self) -> str:
        return json.dumps(
            {
                "total_entries": int(self.total_entries),
                "ways": int(self.ways),
                "value_dim": int(self.value_dim),
                "topic_entries": {
                    str(int(t)): int(c) for t, c in self.topic_entries.items()
                },
                "dynamic_entries": int(self.dynamic_entries),
                "static_entries": int(self.static_entries),
            },
            sort_keys=True,
        )

    @classmethod
    def from_json(cls, s: str) -> "DeviceCacheConfig":
        d = json.loads(s)
        d["topic_entries"] = {int(t): int(c) for t, c in d["topic_entries"].items()}
        return cls(**d)


class STDDeviceCache:
    """Functional cache: state is a pytree of arrays, ops are jittable."""

    def __init__(
        self,
        cfg: DeviceCacheConfig,
        static_hashes: Optional[np.ndarray] = None,
        static_values: Optional[np.ndarray] = None,
    ):
        self.cfg = cfg
        w = cfg.ways
        topics = sorted(cfg.topic_entries)
        self.topic_ids = topics
        self.k = len(topics)
        sets = []
        for t in topics:
            sets.append(max(cfg.topic_entries[t] // w, 1) if cfg.topic_entries[t] > 0 else 0)
        sets.append(max(cfg.dynamic_entries // w, 1) if cfg.dynamic_entries > 0 else 0)
        self.part_sets = np.asarray(sets, dtype=np.int32)
        self.part_offset = np.concatenate([[0], np.cumsum(self.part_sets)]).astype(np.int32)
        self.n_sets = int(self.part_offset[-1])
        #: topic id -> partition index (dynamic = k)
        self.part_of_topic = {t: i for i, t in enumerate(topics)}
        # dense topic -> partition lookup for host routing (parts_for runs
        # on every batch); topics whose partition got zero sets fall
        # through to the dynamic cache at build time, not per batch.
        # Sparse/huge topic-id spans keep the per-topic loop instead of a
        # multi-GB dense table.
        self._part_lut = None
        self._lut_base = 0
        if topics and int(topics[-1]) - int(topics[0]) < (1 << 20):
            self._lut_base = int(topics[0])  # topics is sorted
            lut = np.full(int(topics[-1]) - self._lut_base + 1, self.k, np.int32)
            for t, i in self.part_of_topic.items():
                lut[t - self._lut_base] = i if self.part_sets[i] > 0 else self.k
            self._part_lut = lut
        #: memoized packed static table for the host engine (read-only
        #: layer: rebuild only when a restore swaps the arrays)
        self._static_memo: Tuple[Any, Optional[np.ndarray]] = (None, None)

        if static_hashes is not None and len(static_hashes):
            sh = np.asarray(static_hashes, np.uint64)
            # the empty-slot and pad sentinels can never be static keys
            # (splitmix64 never emits them; guard hand-built hash arrays)
            ok = (sh != 0) & (sh != PAD_H64)
            if static_values is not None:
                static_values = np.asarray(static_values, np.int32)[ok]
            sh = sh[ok]
            order = np.argsort(sh)
            static = sh[order]
            if static_values is None:
                static_values = np.zeros((len(static), cfg.value_dim), np.int32)
            s_vals = np.asarray(static_values, np.int32)[order]
        else:
            static = np.zeros(0, np.uint64)
            s_vals = np.zeros((0, cfg.value_dim), np.int32)
        s_hi, s_lo = pack_hashes(static)
        self.init_state = {
            "ks": jnp.zeros((max(self.n_sets, 1), 4 * w), jnp.uint32),
            "value": jnp.zeros((max(self.n_sets, 1), w, cfg.value_dim), jnp.int32),
            "clock": jnp.zeros((), jnp.int32),
            "static_hi": jnp.asarray(s_hi),
            "static_lo": jnp.asarray(s_lo),
            "static_value": jnp.asarray(s_vals),
        }
        self._part_sets_dev = jnp.asarray(self.part_sets)
        self._part_offset_dev = jnp.asarray(self.part_offset[:-1])

    @classmethod
    def from_spec(
        cls,
        spec,
        stats,
        value_fn=None,
        ways: int = 8,
        value_dim: int = 8,
    ) -> "STDDeviceCache":
        """Build the device cache straight from a declarative spec.

        ``stats`` is the vectorized :class:`repro.core.fast.VecStats`; the
        static array is preloaded with exactly the spec's always-hit set
        (global static + per-topic static fractions), with values from
        ``value_fn(key_ids) -> (n, value_dim)`` when provided.
        """
        cfg = spec.to_device(stats.topic_distinct, ways=ways, value_dim=value_dim)
        static_keys = spec.device_static_keys(stats)
        static_values = value_fn(static_keys) if value_fn is not None else None
        return cls(
            cfg,
            static_hashes=splitmix64(static_keys) if len(static_keys) else None,
            static_values=static_values,
        )

    # -- routing ----------------------------------------------------------

    def parts_for(self, topics: np.ndarray) -> np.ndarray:
        """topic ids (host) -> partition indices (dynamic cache = k)."""
        if self._part_lut is None:  # sparse-id fallback
            out = np.full(len(topics), self.k, dtype=np.int32)
            for t, i in self.part_of_topic.items():
                if self.part_sets[i] > 0:
                    out[np.asarray(topics) == t] = i
            return out
        idx = np.asarray(topics, np.int64) - self._lut_base
        ok = (idx >= 0) & (idx < len(self._part_lut))
        return np.where(
            ok, self._part_lut[np.clip(idx, 0, len(self._part_lut) - 1)], self.k
        ).astype(np.int32)

    # -- jittable ops -------------------------------------------------------

    def _set_index(self, h_lo: jnp.ndarray, part: jnp.ndarray) -> jnp.ndarray:
        n_sets = self._part_sets_dev[part]
        off = self._part_offset_dev[part]
        return off + (h_lo % jnp.maximum(n_sets.astype(jnp.uint32), 1).astype(jnp.uint32)).astype(jnp.int32)

    def static_lookup(self, state, h_hi: jnp.ndarray, h_lo: jnp.ndarray):
        """Vectorized lexicographic binary search over the sorted static set.

        Returns (hit mask, index of the matching entry)."""
        s_hi, s_lo = state["static_hi"], state["static_lo"]
        n = s_hi.shape[0]
        if n == 0:
            z = jnp.zeros(h_hi.shape, jnp.int32)
            return jnp.zeros(h_hi.shape, bool), z
        steps = max(int(np.ceil(np.log2(n + 1))), 1)
        lo = jnp.zeros(h_hi.shape, jnp.int32)
        hi = jnp.full(h_hi.shape, n, jnp.int32)

        def body(_, carry):
            lo, hi = carry
            mid = (lo + hi) // 2
            m_hi = s_hi[jnp.minimum(mid, n - 1)]
            m_lo = s_lo[jnp.minimum(mid, n - 1)]
            less = (m_hi < h_hi) | ((m_hi == h_hi) & (m_lo < h_lo))
            lo = jnp.where(less, mid + 1, lo)
            hi = jnp.where(less, hi, mid)
            return lo, hi

        lo, hi = jax.lax.fori_loop(0, steps, body, (lo, hi))
        idx = jnp.minimum(lo, n - 1)
        return (s_hi[idx] == h_hi) & (s_lo[idx] == h_lo), idx

    def probe(self, state, h_hi, h_lo, part, min_epoch=None):
        """Parallel probe: returns (hit, layer, value, stale).

        layer: 0 = static, 1 = set-associative partition, -1 = miss.
        One gather fetches every probed slot's key, stamp *and* epoch
        words (the packed layout); pad requests never hit.  ``stale``
        marks topic-layer hits whose insertion epoch is below the
        request's ``min_epoch`` floor (all-False when ``min_epoch`` is
        None or zero -- freshness disabled; static entries are read-only
        and never expire).
        """
        pad = (h_hi == PAD_HI) & (h_lo == PAD_LO)
        static_hit, static_idx = self.static_lookup(state, h_hi, h_lo)
        static_hit = static_hit & ~pad
        set_idx = self._set_index(h_lo, part)
        w = self.cfg.ways
        rows = state["ks"][set_idx]  # (B, 4W): one gather
        keys_hi = rows[:, :w]
        keys_lo = rows[:, w : 2 * w]
        match = (keys_hi == h_hi[:, None]) & (keys_lo == h_lo[:, None]) & (keys_hi != 0)
        match = match & ~pad[:, None]
        way_hit = match.any(axis=1)
        way = jnp.argmax(match, axis=1)
        if min_epoch is None:
            stale = jnp.zeros(h_hi.shape, bool)
        else:
            ep = jnp.where(match, rows[:, 3 * w :], 0).max(axis=1)
            stale = way_hit & (ep < min_epoch.astype(jnp.uint32))
        value = state["value"][set_idx, way]
        if state["static_value"].shape[0]:
            value = jnp.where(
                static_hit[:, None], state["static_value"][static_idx], value
            )
        hit = static_hit | way_hit
        layer = jnp.where(static_hit, 0, jnp.where(way_hit, 1, -1))
        return hit, layer, value, stale

    def commit(self, state, h_hi, h_lo, part, values, admit, epochs=None, min_epoch=None):
        """Serialized batch update preserving exact W-way LRU order.

        Hits refresh stamps; admitted misses evict the LRU way of their
        set.  Items are processed in request order (fori_loop), so two
        same-set requests in one batch behave exactly like back-to-back
        requests in the sequential simulator.  This is the *oracle*: it
        runs on the unpacked (key_hi, key_lo, stamp, epoch) view via the
        exact pack/unpack adapters, so the packed engines are
        property-tested against unchanged reference semantics.  Pad
        requests are inert.  A hit whose resident epoch is below
        ``min_epoch[i]`` is stale: its value slot and epoch are rewritten
        from ``values[i]`` / ``epochs[i]`` (both default to zeros --
        freshness off).
        """
        b = h_hi.shape[0]
        static_hit, _ = self.static_lookup(state, h_hi, h_lo)
        set_idx = self._set_index(h_lo, part)
        key_hi0, key_lo0, stamp0 = unpack_words(state["ks"])
        epoch0 = unpack_epoch(state["ks"])
        pad = (h_hi == PAD_HI) & (h_lo == PAD_LO)
        if epochs is None:
            epochs = jnp.zeros((b,), jnp.uint32)
        if min_epoch is None:
            min_epoch = jnp.zeros((b,), jnp.uint32)
        # effective write epoch (mirrors probe_and_commit_op): a pristine
        # fresh hit keeps its resident epoch, so a mid-batch evict +
        # re-insert cannot extend the entry's lifetime past its original
        # insertion; conservative in the rare race, uniform across engines
        sc0 = jnp.minimum(set_idx, key_hi0.shape[0] - 1)
        p_hi0, p_lo0 = key_hi0[sc0], key_lo0[sc0]
        pm0 = (p_hi0 == h_hi[:, None]) & (p_lo0 == h_lo[:, None]) & (p_hi0 != 0)
        pm0 = pm0 & ~pad[:, None]
        pm0_ep = jnp.where(pm0, epoch0[sc0], 0).max(axis=1)
        epochs = jnp.where(
            pm0.any(axis=1) & (pm0_ep >= min_epoch), pm0_ep, epochs
        ).astype(jnp.uint32)

        def body(i, st):
            key_hi, key_lo, stamp, epoch, value, clock = st
            s = set_idx[i]
            row_hi = key_hi[s]
            row_lo = key_lo[s]
            match = (row_hi == h_hi[i]) & (row_lo == h_lo[i]) & (row_hi != 0) & ~pad[i]
            is_hit = match.any()
            way_h = jnp.argmax(match, axis=0)
            way_e = jnp.argmin(stamp[s], axis=0)
            do_write = (~static_hit[i]) & ~pad[i] & (is_hit | admit[i])
            way = jnp.where(is_hit, way_h, way_e)
            stale = is_hit & (epoch[s, way] < min_epoch[i])
            refresh = do_write & (~is_hit | stale)
            new_stamp = clock + 1 + i
            key_hi = key_hi.at[s, way].set(jnp.where(do_write, h_hi[i], key_hi[s, way]))
            key_lo = key_lo.at[s, way].set(jnp.where(do_write, h_lo[i], key_lo[s, way]))
            stamp = stamp.at[s, way].set(jnp.where(do_write, new_stamp, stamp[s, way]))
            epoch = epoch.at[s, way].set(jnp.where(refresh, epochs[i], epoch[s, way]))
            value = value.at[s, way].set(
                jnp.where(refresh, values[i], value[s, way])
            )
            return key_hi, key_lo, stamp, epoch, value, clock

        key_hi, key_lo, stamp, epoch, value, clock = jax.lax.fori_loop(
            0,
            b,
            body,
            (key_hi0, key_lo0, stamp0, epoch0, state["value"], state["clock"]),
        )
        out = dict(state)
        out.update(
            ks=pack_words(key_hi, key_lo, stamp, epoch), value=value, clock=clock + b
        )
        return out

    def commit_vectorized(
        self, state, h_hi, h_lo, part, values, admit, epochs=None, min_epoch=None,
        use_kernel: bool = False, interpret: Optional[bool] = None, bm: int = 256,
    ):
        """Conflict-aware batch commit, bit-exact with :meth:`commit`.

        The batch is stable-sorted by set index, within-batch conflicts
        are resolved by replaying each set's requests round-by-round
        (sequential depth = deepest conflict, not batch size), and the
        result lands in one gather/compute/scatter over the packed state.
        Values are applied by the deferred fill (:meth:`fill_values`):
        last insert (or stale refresh) per slot wins, which is exactly
        the order the fori_loop writes them.
        """
        b = h_hi.shape[0]
        if b == 0:
            return dict(state)
        static_hit, _ = self.static_lookup(state, h_hi, h_lo)
        set_idx = self._set_index(h_lo, part)
        out = probe_and_commit_op(
            state["ks"], h_hi, h_lo, set_idx, admit, static_hit, state["clock"],
            epochs=epochs, min_epoch=min_epoch,
            use_kernel=use_kernel, interpret=interpret, bm=bm,
        )
        new = dict(state)
        new.update(ks=out["ks"], clock=state["clock"] + b)
        return self.fill_values(new, set_idx, out["wrote"], out["way"], values)

    def probe_and_commit(
        self, state, h_hi, h_lo, part, admit, epochs=None, min_epoch=None,
        use_kernel: bool = False, interpret: Optional[bool] = None, bm: int = 256,
    ):
        """Fused serve step: probe + key/stamp commit in one device call.

        Returns ``(hit, layer, value, stale, new_state, (set_idx, wrote,
        way))``.  ``hit``/``layer``/``value``/``stale`` are identical to
        :meth:`probe` against the pre-commit state (atomic batch probe);
        the commit replays the batch in arrival order like :meth:`commit`
        with one twist forced by causality: an admitted miss's (or stale
        refresh's) value does not exist yet (the backend produces it
        after the probe), so inserts land keys and stamps now and the
        caller scatters values afterwards via :meth:`fill_values` with
        the returned ``(set_idx, wrote, way)``.  The freshness check
        rides the op's existing single gather -- no extra device work.
        """
        b = h_hi.shape[0]
        pad = (h_hi == PAD_HI) & (h_lo == PAD_LO)
        static_hit, static_idx = self.static_lookup(state, h_hi, h_lo)
        static_hit = static_hit & ~pad
        set_idx = self._set_index(h_lo, part)
        out = probe_and_commit_op(
            state["ks"], h_hi, h_lo, set_idx, admit, static_hit, state["clock"],
            epochs=epochs, min_epoch=min_epoch,
            use_kernel=use_kernel, interpret=interpret, bm=bm,
        )
        value = state["value"][set_idx, out["pre_way"]]
        if state["static_value"].shape[0]:
            value = jnp.where(
                static_hit[:, None], state["static_value"][static_idx], value
            )
        hit = static_hit | out["pre_hit"]
        layer = jnp.where(static_hit, 0, jnp.where(out["pre_hit"], 1, -1))
        new = dict(state)
        new.update(ks=out["ks"], clock=state["clock"] + b)
        return (
            hit, layer, value, out["pre_stale"], new,
            (set_idx, out["wrote"], out["way"]),
        )

    def fill_probe_and_commit(
        self, state, f_set_idx, f_wrote, f_way, f_values, h_hi, h_lo, part, admit,
        epochs=None, min_epoch=None,
        use_kernel: bool = False, interpret: Optional[bool] = None, bm: int = 256,
    ):
        """Double-buffered serve step: apply the *previous* batch's
        deferred value fill, then probe-and-commit the current batch, in
        one device call.

        The fill lands before the probe reads ``value``, so a query
        hitting a key the previous batch inserted (or revalidated) sees
        its backend result -- semantics identical to :meth:`fill_values`
        followed by :meth:`probe_and_commit`, minus one dispatch, and XLA
        overlaps the value scatter with the next bucket's key/stamp
        gather.  The fill plan must be padded to the current bucket's
        length (pad entries carry ``f_wrote == False``).
        """
        state = self.fill_values(state, f_set_idx, f_wrote, f_way, f_values)
        return self.probe_and_commit(
            state, h_hi, h_lo, part, admit, epochs=epochs, min_epoch=min_epoch,
            use_kernel=use_kernel, interpret=interpret, bm=bm,
        )

    def serve_one_call(
        self, state, f_set_idx, f_wrote, f_way, f_values, h_hi, h_lo, part, admit,
        epochs=None, min_epoch=None,
        use_kernel: bool = False, interpret: Optional[bool] = None, bm: int = 256,
    ):
        """One-dispatch serve step: the previous batch's deferred value
        fill, the atomic probe (with freshness), the conflict-aware
        commit, and the probed value-row gather, all through
        :func:`repro.kernels.cache_ops.serve_fused_op` -- one Pallas
        kernel under ``use_kernel``, one fused XLA program otherwise.

        Same signature and return contract as
        :meth:`fill_probe_and_commit` (``(hit, layer, value, stale,
        new_state, (set_idx, wrote, way))``), and bit-exact with it: the
        fill lands before the probe reads any value row, so a query
        hitting a key the previous batch inserted sees its backend
        result.  An all-``False`` fill plan degenerates to a plain fused
        serve, which is what lets the broker keep **one** compiled entry
        point per bucket shape instead of two (``fused`` +
        ``fused_fill``) -- and exactly one device dispatch per served
        batch.  The plan must be padded to batch length (pad entries
        carry ``f_wrote == False``).
        """
        b = h_hi.shape[0]
        pad = (h_hi == PAD_HI) & (h_lo == PAD_LO)
        static_hit, static_idx = self.static_lookup(state, h_hi, h_lo)
        static_hit = static_hit & ~pad
        set_idx = self._set_index(h_lo, part)
        out = serve_fused_op(
            state["ks"], state["value"], h_hi, h_lo, set_idx, admit, static_hit,
            state["clock"],
            f_set_idx=f_set_idx, f_wrote=f_wrote, f_way=f_way, f_values=f_values,
            epochs=epochs, min_epoch=min_epoch,
            use_kernel=use_kernel, interpret=interpret, bm=bm,
        )
        value = out["values"]
        if state["static_value"].shape[0]:
            value = jnp.where(
                static_hit[:, None], state["static_value"][static_idx], value
            )
        hit = static_hit | out["pre_hit"]
        layer = jnp.where(static_hit, 0, jnp.where(out["pre_hit"], 1, -1))
        new = dict(state)
        new.update(ks=out["ks"], value=out["value"], clock=state["clock"] + b)
        return (
            hit, layer, value, out["pre_stale"], new,
            (set_idx, out["wrote"], out["way"]),
        )

    def fill_values(self, state, set_idx, wrote, way, values):
        """Deferred value fill for inserts reported by the fused commit.

        Scatters ``values[i]`` into slot ``(set_idx[i], way[i])`` for every
        request with ``wrote[i]``, resolving slot collisions to the last
        writer in batch order -- the value the sequential commit would
        have left behind.
        """
        w = state["value"].shape[1]
        nslots = state["value"].shape[0] * w
        b = set_idx.shape[0]
        slot = jnp.where(wrote, set_idx * w + way, nslots)
        pos = jnp.arange(b, dtype=jnp.int32)
        last = jnp.full((nslots,), -1, jnp.int32).at[slot].max(pos, mode="drop")
        winner = wrote & (last[jnp.minimum(slot, nslots - 1)] == pos)
        flat = state["value"].reshape(nslots, -1)
        flat = flat.at[jnp.where(winner, slot, nslots)].set(values, mode="drop")
        out = dict(state)
        out["value"] = flat.reshape(state["value"].shape)
        return out

    # -- host engine --------------------------------------------------------
    #
    # The same conflict-aware algorithm (stable sort by set, round-by-round
    # segmented replay, gather/compute/scatter), executed by numpy.  On CPU
    # backends XLA prices a B-index scatter at ~170ns/index and a stable
    # argsort at ~1.4ms (B=4096), so the jnp vectorized path cannot beat
    # the compiled fori_loop; numpy's native sort (~0.1ms) and fancy
    # scatter (~10us) can, by an order of magnitude.  The broker picks
    # this engine automatically when jax's default backend is "cpu"; on
    # accelerators the jnp/Pallas paths run.  Bit-exact with `commit`
    # (shared property tests).  The unpacked (key_hi, key_lo, stamp)
    # arrays the replay mutates are numpy *views* into the packed ``ks``.

    def _set_index_host(self, h_lo: np.ndarray, part: np.ndarray) -> np.ndarray:
        n_sets = self.part_sets[part]
        off = self.part_offset[part]  # offsets: first k+1 entries of the cumsum
        mod = np.maximum(n_sets.astype(np.uint32), 1)
        return (off + (h_lo.astype(np.uint32) % mod).astype(np.int32)).astype(np.int32)

    def static_lookup_host(self, state, h_hi: np.ndarray, h_lo: np.ndarray):
        src = state["static_hi"]
        if self._static_memo[0] is src:
            table = self._static_memo[1]
        else:  # read-only layer: packed once, rebuilt only after a restore
            s_hi = np.asarray(src, np.uint64)
            s_lo = np.asarray(state["static_lo"], np.uint64)
            table = (s_hi << np.uint64(32)) | s_lo
            self._static_memo = (src, table)
        if table.shape[0] == 0:
            z = np.zeros(h_hi.shape, np.int32)
            return np.zeros(h_hi.shape, bool), z
        q = (h_hi.astype(np.uint64) << np.uint64(32)) | h_lo.astype(np.uint64)
        idx = np.searchsorted(table, q)
        idx = np.minimum(idx, len(table) - 1).astype(np.int32)
        return table[idx] == q, idx

    def _resolve_host(
        self, key_hi, key_lo, stamp, epoch, h_hi, h_lo, set_idx, admit, static_hit,
        clock, epochs=None, min_epoch=None, depth_limit: Optional[int] = None,
    ):
        """Segmented replay on host arrays; mutates key/stamp/epoch arrays
        in place.

        Round j applies every set's j-th request, narrowed to the items
        still active -- total work is O(B * W), and the sort is numpy's.
        Returns the per-request write plan for the deferred value fill, or
        ``None`` (before touching the arrays) when the conflict depth
        exceeds ``depth_limit``.
        """
        b = len(h_hi)
        if b == 0:
            return np.zeros(0, bool), np.zeros(0, np.int32)
        if epochs is None:
            epochs = np.zeros(b, np.uint32)
        if min_epoch is None:
            min_epoch = np.zeros(b, np.uint32)
        pad = (h_hi == PAD_HI) & (h_lo == PAD_LO)
        s_max = key_hi.shape[0] - 1
        sc = np.minimum(set_idx, s_max)  # jnp gathers clamp ...
        oob = set_idx > s_max  # ... and scatters drop
        wrote = np.zeros(b, bool)
        way_out = np.zeros(b, np.int32)
        # pads, static hits and out-of-range sets never write and never
        # affect any other request's replay (``do_write`` masks all
        # three), so they leave the conflict ranking entirely: a bucketed
        # slice can be half pad, and every pad shares one set index, so
        # each would otherwise cost a full python round -- and an all-pad
        # warmup batch would trip the depth cutoff into the compiled
        # oracle for nothing
        act = np.flatnonzero(~(pad | static_hit | oob))
        if len(act) == 0:
            return wrote, way_out
        # 16-bit radix argsort when set indices fit (they do until the
        # cache crosses 65k sets / ~0.5M entries per host)
        sc_a = sc[act]
        sort_key = sc_a.astype(np.uint16) if s_max < 0xFFFF else sc_a
        order = act[np.argsort(sort_key, kind="stable")]
        ss_c = sc[order]
        n_act = len(act)
        start = np.empty(n_act, bool)
        start[0] = True
        start[1:] = ss_c[1:] != ss_c[:-1]
        ar = np.arange(n_act)
        rank = ar - np.maximum.accumulate(np.where(start, ar, 0))
        depth = int(rank.max()) + 1
        if depth_limit is not None and depth > depth_limit:
            return None
        # effective write epoch (mirrors probe_and_commit_op), computed
        # against the still-pristine arrays before any round mutates them
        pm0 = (key_hi[sc] == h_hi[:, None]) & (key_lo[sc] == h_lo[:, None]) \
            & (key_hi[sc] != 0)
        pm0 &= ~pad[:, None]
        pm0_ep = np.where(pm0, epoch[sc], 0).max(axis=1)
        epochs = np.where(
            pm0.any(axis=1) & (pm0_ep >= min_epoch), pm0_ep, epochs
        ).astype(np.uint32)
        clock = np.int32(clock)
        for j in range(depth):
            i = order[np.flatnonzero(rank == j)]  # round j, arrival order kept
            s = sc[i]
            rh, rl, rst = key_hi[s], key_lo[s], stamp[s]
            m = (rh == h_hi[i][:, None]) & (rl == h_lo[i][:, None]) & (rh != 0)
            m &= ~pad[i][:, None]
            # one reduction finds both outcomes: a match outranks every
            # stamp (stamps are >= 0), else the LRU way wins; ties keep
            # the first index exactly like the oracle's argmin/argmax
            prio = np.where(m, np.int32(-1), rst)
            way = prio.argmin(axis=1).astype(np.int32)
            is_hit = prio[np.arange(len(i)), way] == -1
            stale = is_hit & (epoch[s, way] < min_epoch[i])
            do_write = ~static_hit[i] & ~pad[i] & (is_hit | admit[i]) & ~oob[i]
            refresh = do_write & (~is_hit | stale)
            w = np.flatnonzero(do_write)
            key_hi[s[w], way[w]] = h_hi[i[w]]
            key_lo[s[w], way[w]] = h_lo[i[w]]
            stamp[s[w], way[w]] = (clock + 1 + i[w]).astype(np.int32)
            r = np.flatnonzero(refresh)
            epoch[s[r], way[r]] = np.asarray(epochs)[i[r]]
            wrote[i] = refresh
            way_out[i] = way
        return wrote, way_out

    @staticmethod
    def _own(arr, dtype, inplace: bool) -> np.ndarray:
        """A writable numpy array for ``arr``: in place when the caller owns
        the state (the serving contract ``state = commit(state, ...)``
        consumes the old state, like jit donation), a copy otherwise."""
        a = np.asarray(arr, dtype)
        if inplace and isinstance(arr, np.ndarray) and a.flags.writeable:
            return a
        return np.array(a)

    #: conflict depths past this dispatch to the fori_loop oracle -- the
    #: replay is sequential by data dependency there, and the compiled
    #: loop beats b python-level rounds
    HOST_DEPTH_LIMIT = 64

    def commit_host(
        self, state, h_hi, h_lo, part, values, admit, epochs=None, min_epoch=None,
        inplace: bool = False,
    ):
        """Numpy engine for :meth:`commit_vectorized`; bit-exact with both.

        Batches whose deepest set conflict exceeds ``HOST_DEPTH_LIMIT``
        are handed to the jitted sequential oracle: past that depth the
        replay is inherently sequential and the compiled loop wins.
        """
        h_hi, h_lo = np.asarray(h_hi), np.asarray(h_lo)
        b = len(h_hi)
        out = dict(state)
        out["clock"] = np.int32(state["clock"]) + np.int32(b)
        if b == 0:
            return out
        if epochs is None:
            epochs = np.zeros(b, np.uint32)
        if min_epoch is None:
            min_epoch = np.zeros(b, np.uint32)
        static_hit, _ = self.static_lookup_host(state, h_hi, h_lo)
        set_idx = self._set_index_host(h_lo, np.asarray(part))
        ks = self._own(state["ks"], np.uint32, inplace)
        key_hi, key_lo, stamp = unpack_words(ks)  # in-place views
        epoch = unpack_epoch(ks)
        plan = self._resolve_host(
            key_hi, key_lo, stamp, epoch, h_hi, h_lo, set_idx, np.asarray(admit),
            static_hit, state["clock"], epochs=np.asarray(epochs, np.uint32),
            min_epoch=np.asarray(min_epoch, np.uint32),
            depth_limit=self.HOST_DEPTH_LIMIT,
        )
        if plan is None:  # pathological depth: sequential oracle
            if not hasattr(self, "_oracle_jit"):
                self._oracle_jit = jax.jit(self.commit)
            return self._oracle_jit(
                {k: jnp.asarray(v) for k, v in state.items()},
                jnp.asarray(h_hi), jnp.asarray(h_lo), jnp.asarray(part),
                jnp.asarray(values), jnp.asarray(admit),
                jnp.asarray(epochs, jnp.uint32), jnp.asarray(min_epoch, jnp.uint32),
            )
        wrote, way = plan
        value = self._own(state["value"], np.int32, inplace)
        w = np.flatnonzero(wrote & (set_idx <= ks.shape[0] - 1))
        value[set_idx[w], way[w]] = np.asarray(values)[w]  # in order: last insert wins
        out.update(ks=ks, value=value)
        return out

    def probe_and_commit_host(
        self, state, h_hi, h_lo, part, admit, epochs=None, min_epoch=None,
        inplace: bool = False,
    ):
        """Numpy engine for :meth:`probe_and_commit`: same contract, no jit.

        Everything runs on host arrays -- the CPU serving fast path.  The
        returned state holds numpy arrays (zero-copy for the next host
        call; ``jnp.asarray`` on demand for checkpointing).
        """
        h_hi, h_lo = np.asarray(h_hi), np.asarray(h_lo)
        b = len(h_hi)
        if epochs is None:
            epochs = np.zeros(b, np.uint32)
        if min_epoch is None:
            min_epoch = np.zeros(b, np.uint32)
        epochs = np.asarray(epochs, np.uint32)
        min_epoch = np.asarray(min_epoch, np.uint32)
        pad = (h_hi == PAD_HI) & (h_lo == PAD_LO)
        static_hit, static_idx = self.static_lookup_host(state, h_hi, h_lo)
        static_hit = static_hit & ~pad
        set_idx = self._set_index_host(h_lo, np.asarray(part))
        ks_pre = np.asarray(state["ks"])
        w = self.cfg.ways
        s_max = ks_pre.shape[0] - 1
        sc = np.minimum(set_idx, s_max)
        rows = ks_pre[sc]  # (B, 4W): one gather for keys, stamps and epochs
        pre_rh = rows[:, :w]
        pre_rl = rows[:, w : 2 * w]
        pm = (pre_rh == h_hi[:, None]) & (pre_rl == h_lo[:, None]) & (pre_rh != 0)
        pm &= ~pad[:, None]
        pre_hit = pm.any(axis=1)
        pre_way = pm.argmax(axis=1).astype(np.int32)
        pre_ep = np.where(pm, rows[:, 3 * w :], 0).max(axis=1)
        pre_stale = pre_hit & (pre_ep < min_epoch)
        value = np.asarray(state["value"])[sc, pre_way]
        if np.asarray(state["static_value"]).shape[0]:
            value = np.where(
                static_hit[:, None], np.asarray(state["static_value"])[static_idx], value
            )
        ks = self._own(state["ks"], np.uint32, inplace)
        key_hi, key_lo, stamp = unpack_words(ks)  # in-place views
        epoch = unpack_epoch(ks)
        plan = self._resolve_host(
            key_hi, key_lo, stamp, epoch, h_hi, h_lo, set_idx, np.asarray(admit),
            static_hit, state["clock"], epochs=epochs, min_epoch=min_epoch,
            depth_limit=self.HOST_DEPTH_LIMIT,
        )
        if plan is None:
            # pathological conflict depth (skewed traffic flooding one
            # set): the replay is sequential by data dependency, so run
            # the compiled per-request loop, which also emits the plan
            if not hasattr(self, "_fused_seq_jit"):
                self._fused_seq_jit = jax.jit(_sequential_replay)
            r_hi, r_lo, r_st, r_ep, wrote, way = self._fused_seq_jit(
                jnp.asarray(key_hi), jnp.asarray(key_lo),
                jnp.asarray(stamp), jnp.asarray(epoch),
                jnp.asarray(h_hi), jnp.asarray(h_lo),
                jnp.asarray(set_idx), jnp.asarray(admit), jnp.asarray(static_hit),
                jnp.asarray(state["clock"]),
                jnp.asarray(epochs), jnp.asarray(min_epoch),
            )
            key_hi[...] = np.asarray(r_hi)  # write back through the ks views
            key_lo[...] = np.asarray(r_lo)
            stamp[...] = np.asarray(r_st)
            epoch[...] = np.asarray(r_ep)
            wrote, way = np.asarray(wrote), np.asarray(way)
        else:
            wrote, way = plan
        hit = static_hit | pre_hit
        layer = np.where(static_hit, 0, np.where(pre_hit, 1, -1)).astype(np.int32)
        new = dict(state)
        new.update(ks=ks, clock=np.int32(state["clock"]) + np.int32(b))
        return hit, layer, value, pre_stale, new, (set_idx, wrote, way)

    def fill_values_host(self, state, set_idx, wrote, way, values, inplace: bool = False):
        value = self._own(state["value"], np.int32, inplace)
        w = np.flatnonzero(np.asarray(wrote) & (set_idx <= value.shape[0] - 1))
        value[set_idx[w], np.asarray(way)[w]] = np.asarray(values)[w]
        out = dict(state)
        out["value"] = value
        return out

    # -- elastic re-partitioning -------------------------------------------

    def repartition(
        self, state, new_cfg: DeviceCacheConfig, engine: str = "vec",
        bucket=None,
    ) -> Tuple["STDDeviceCache", Any]:
        """Rebuild the partition table (e.g., fresh topic popularity) and
        migrate resident entries, preserving recency order.

        Live entries are bulk-inserted into the new layout oldest-first so
        the newest survive a shrinking partition -- exactly the eviction
        order a sequential replay would produce.  The static layer is
        read-only and carried over untouched (hashes *and* values), as is
        the recency clock's monotonicity (the new clock restarts at the
        number of migrated entries; stamps stay strictly increasing in
        migration order).

        ``engine`` picks the bulk-insert path: ``"vec"`` (the jnp
        vectorized commit), ``"host"`` (the numpy engine the broker uses
        on CPU backends), ``"oracle"`` (the fori_loop reference) -- all
        bit-exact with each other (property-tested), so a live rebalance
        lands the same state whichever engine the broker serves with.

        ``bucket`` (a :class:`repro.serving.spec.BucketSpec`) pads the
        migration batch up to a shape bucket with the reserved pad key,
        so the resident-count-dependent bulk insert reuses a bucketed
        trace instead of compiling a fresh shape per migration.  Pad
        migrants are inert by the engine contract; the migrated state is
        identical either way (stamps included: pads sit at the batch
        tail, after every real migrant's arrival position).
        """
        if engine not in ("vec", "host", "oracle"):
            raise ValueError(f"engine must be vec|host|oracle, got {engine!r}")
        new_cache = STDDeviceCache(new_cfg, static_hashes=None)
        new_state = dict(new_cache.init_state)
        new_state["static_hi"] = state["static_hi"]
        new_state["static_lo"] = state["static_lo"]
        new_state["static_value"] = state["static_value"]
        h64, topics, vals, eps, _ = self.extract_live(state)
        new_state = new_cache.bulk_insert(
            new_state, h64, topics, vals, epochs=eps, engine=engine, bucket=bucket
        )
        return new_cache, new_state

    def extract_live(self, state):
        """Live dynamic/topic-layer entries of ``state``, oldest-first.

        Returns ``(h64, topics, values, epochs, stamps)``: the 64-bit
        hashes reassembled from the stored key words, the recovered
        topics (:data:`DYNAMIC` for dynamic-partition entries), the
        cached values, the insertion epochs, and the recency stamps,
        sorted by stamp ascending -- the replay order a bulk insert
        needs so the newest entries survive a shrinking target.  The
        static layer is excluded: it is read-only and rebuilt at deploy
        time, not migrated.  This is the extraction half of
        :meth:`repartition`; cross-shard resharding calls it per shard,
        merges on the stamps, and re-routes on the hash words (no
        original query ids needed).
        """
        ks_np = np.asarray(state["ks"])
        key_hi, key_lo, stamp = unpack_words(ks_np)
        epoch = np.asarray(unpack_epoch(ks_np))
        value = np.asarray(state["value"])
        # partition of each old set
        old_part = np.searchsorted(self.part_offset[1:], np.arange(self.n_sets), side="right")
        live = key_hi != 0
        order = np.argsort(stamp[live])  # oldest first so newest survive
        sets_l, ways_l = np.nonzero(live)
        sets_l, ways_l = sets_l[order], ways_l[order]
        h64 = (key_hi[sets_l, ways_l].astype(np.uint64) << np.uint64(32)) | key_lo[
            sets_l, ways_l
        ].astype(np.uint64)
        parts = old_part[sets_l].astype(np.int32)
        topics = np.full(len(parts), DYNAMIC, dtype=np.int64)
        for t, i in self.part_of_topic.items():
            topics[parts == i] = t
        return (
            h64,
            topics,
            value[sets_l, ways_l],
            epoch[sets_l, ways_l].astype(np.uint32),
            stamp[sets_l, ways_l].astype(np.int64),
        )

    def bulk_insert(
        self, state, h64, topics, values, epochs=None, engine: str = "vec",
        bucket=None,
    ):
        """Insert pre-hashed entries through a commit engine, in order.

        The insertion half of :meth:`repartition`: entries arrive as
        ``(h64, topic, value[, epoch])`` tuples (typically from
        :meth:`extract_live`, possibly merged across several source
        caches) and land through the same bucket-padded commit path a
        live migration uses, so a bulk insert is bit-exact with serving
        the entries as admitted misses in that order.  Inserted entries
        keep their given insertion epochs: a migration moves capacity,
        it does not renew TTLs (entries that were nearly stale stay
        nearly stale -- see docs/freshness.md).  Returns the new state.
        """
        if engine not in ("vec", "host", "oracle"):
            raise ValueError(f"engine must be vec|host|oracle, got {engine!r}")
        h64 = np.asarray(h64, np.uint64)
        parts = self.parts_for(np.asarray(topics, np.int64))
        hi = (h64 >> np.uint64(32)).astype(np.uint32)
        lo = (h64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        vals = np.asarray(values, np.int32)
        eps = (
            np.asarray(epochs, np.uint32)
            if epochs is not None
            else np.zeros(len(hi), np.uint32)
        )
        admit = np.ones(len(hi), bool)
        # static-shape contract: pad the migration batch to its bucket
        bp = bucket.padded_len(len(hi)) if bucket is not None else len(hi)
        n_real = len(hi)
        hi, lo, parts, vals, admit = pad_batch(
            hi, lo, parts, self.k, bp, values=vals, admit=admit
        )
        if bp > n_real:
            eps = np.concatenate([eps, np.zeros(bp - n_real, np.uint32)])
        if engine == "host":
            return self.commit_host(
                state, hi, lo, parts, vals, admit, epochs=eps, inplace=True
            )
        if engine == "oracle":
            return self.commit(
                state, jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(parts),
                jnp.asarray(vals), jnp.asarray(admit), epochs=jnp.asarray(eps),
            )
        return self.commit_vectorized(
            state, jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(parts),
            jnp.asarray(vals), jnp.asarray(admit), epochs=jnp.asarray(eps),
        )

    # -- control-plane invalidation ----------------------------------------

    def invalidate_keys(self, state, h_hi, h_lo, part) -> Tuple[Dict[str, Any], int]:
        """Point invalidation: zero the key words of matching resident
        slots (key 0 = empty), leaving stamps/epochs/values to be
        overwritten by the next insert.

        Runs host-side by design -- invalidation events are control-plane
        traffic, orders of magnitude rarer than serves, so a device
        round-trip here is cheaper than widening the hot-path kernel.
        Duplicated keys in the batch are idempotent.  Returns
        ``(new_state, n_slots_zeroed)``; the returned ``ks`` stays numpy
        (host engine zero-copy; jit consumers convert on entry).
        """
        h_hi, h_lo = np.asarray(h_hi, np.uint32), np.asarray(h_lo, np.uint32)
        ks = np.array(np.asarray(state["ks"]), np.uint32)  # owned host copy
        key_hi, key_lo, _ = unpack_words(ks)
        set_idx = self._set_index_host(h_lo, np.asarray(part))
        s_max = ks.shape[0] - 1
        sc = np.minimum(set_idx, s_max)
        pad = (h_hi == PAD_HI) & (h_lo == PAD_LO)
        rows_hi = key_hi[sc]
        rows_lo = key_lo[sc]
        m = (rows_hi == h_hi[:, None]) & (rows_lo == h_lo[:, None]) & (rows_hi != 0)
        m &= ~(pad | (set_idx > s_max))[:, None]
        req, way = np.nonzero(m)
        n = len(np.unique(sc[req].astype(np.int64) * self.cfg.ways + way))
        key_hi[sc[req], way] = 0
        key_lo[sc[req], way] = 0
        out = dict(state)
        out["ks"] = ks
        return out, int(n)
