"""Plain reference of what the served STD cache must answer.

Written from the cache's stated semantics, with nothing taken from the
program: its own hash, its own layout of the layers and its own LRU.

* **Layers** (Mele et al., strategy STDv_SDC_C2).  ``N`` entries split
  into a static layer of ``round(f_s N)`` entries, a topic layer of
  ``round(f_t N)`` and a dynamic layer of the rest.  The static layer
  holds the most frequent training queries (ties by query id).  Each
  topic's share of the topic layer follows its count of distinct
  training queries; a fraction ``f_ts`` of each share is static too,
  filled with the topic's most frequent queries outside the global
  static set, and the rest is an LRU section.  Two apportionments are
  stated: the static fractions follow the paper's nearest-integer
  shares, and the LRU sections' address space follows largest-remainder
  shares (which tile it exactly).
* **Sets.**  Each LRU section is ``max(entries // W, 1)`` sets of ``W``
  ways (none when it has no entries); a query's set is its section's
  offset plus the low 32 bits of its splitmix64 hash modulo the
  section's sets.  A query with no topic, or whose topic has no sets,
  goes to the dynamic section.
* **Shards.**  With ``S`` hash-routed shards a query goes to shard
  ``(hash >> 32) mod S``; shard ``i`` holds ``N // S`` entries (one more
  for the first ``N mod S``), laid out as above from the same training
  statistics, and the static keys of the whole cache that route to it.
* **A batch** is probed atomically: a request hits when its query is
  static or resident in its set when the batch starts.  Then every
  request that is not static updates its set in arrival order, as exact
  LRU: a resident query becomes most recent, a missing one is inserted
  and evicts the least recent when the set is full.
* **Values.**  Every answer, hit or miss, is the backend's answer for
  that query.

``replay`` gives the hit mask of a served sequence.  ``refresh=False``
gives the control: hits that do not refresh recency (FIFO sets), the
shortcut that would save the commit of every hit.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

NO_TOPIC = -1
_M64 = np.uint64(0xFFFFFFFFFFFFFFFF)


def splitmix64(q: np.ndarray) -> np.ndarray:
    """The splitmix64 finalizer of non-negative query ids.  The two hash
    values the cache reserves (0 for an empty way, all ones for padding)
    are moved off: 0 to 1, all ones to all ones but the last bit."""
    x = np.asarray(q, np.int64).astype(np.uint64)
    with np.errstate(over="ignore"):
        z = x + np.uint64(0x9E3779B97F4A7C15)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    z = z ^ (z >> np.uint64(31))
    z[z == 0] = 1
    z[z == _M64] = _M64 ^ np.uint64(1)
    return z


def _shares(total: int, distinct: Dict[int, int]) -> Tuple[list, np.ndarray, np.ndarray]:
    topics = sorted(distinct)
    counts = np.array([distinct[t] for t in topics], np.float64)
    q = counts.sum()
    if total == 0 or q <= 0:
        return topics, counts, np.zeros(len(topics))
    return topics, counts, total * counts / q


def nearest_shares(total: int, distinct: Dict[int, int]) -> Dict[int, int]:
    """Each topic's entries rounded to the nearest integer, halves up."""
    topics, _, shares = _shares(total, distinct)
    return {t: int(s) for t, s in zip(topics, np.floor(shares + 0.5))}


def exact_shares(total: int, distinct: Dict[int, int]) -> Dict[int, int]:
    """Largest-remainder shares that sum to ``total``; ties go to the
    larger fraction, then the more distinct queries, then the lower id."""
    topics, counts, shares = _shares(total, distinct)
    base = np.floor(shares).astype(np.int64)
    rem = int(total - base.sum()) if len(topics) and counts.sum() > 0 else 0
    if rem > 0:
        order = np.lexsort((np.arange(len(topics)), -counts, -(shares - base)))
        base[order[:rem]] += 1
    return {t: int(s) for t, s in zip(topics, base)}


@dataclasses.dataclass
class Layout:
    """The sets of every shard, numbered globally."""

    static_hashes: np.ndarray  # sorted uint64
    shards: int
    ways: int
    #: per shard: topic -> (first global set, number of sets)
    sections: List[Dict[int, Tuple[int, int]]]
    #: per shard: the dynamic section's (first global set, number of sets)
    dynamic: List[Tuple[int, int]]
    n_sets: int


def _layer_sizes(n: int, f_s: float, f_t: float) -> Tuple[int, int, int]:
    s = min(int(round(f_s * n)), n)
    t = min(int(round(f_t * n)), n - s)
    return s, t, n - s - t


def build_layout(
    train_keys: np.ndarray, key_topic: np.ndarray, entries: int, f_s: float,
    f_t: float, f_ts: float, ways: int, shards: int = 1,
) -> Layout:
    nq = len(key_topic)
    freq = np.bincount(np.asarray(train_keys, np.int64), minlength=nq)
    by_freq = np.lexsort((np.arange(nq), -freq))
    rank = np.empty(nq, np.int64)
    rank[by_freq] = np.arange(nq)
    topic = np.asarray(key_topic, np.int64)
    distinct = {
        int(t): int(((topic == t) & (freq > 0)).sum())
        for t in np.unique(topic[topic != NO_TOPIC])
    }

    n_s, n_t, _ = _layer_sizes(entries, f_s, f_t)
    static = (rank < n_s) & (freq > 0)
    glob = static.copy()
    for t, c in nearest_shares(n_t, distinct).items():
        m = int(round(f_ts * c))
        elig = (topic == t) & ~glob
        static[by_freq[elig[by_freq]][:m]] = True
    static_hashes = np.sort(splitmix64(np.flatnonzero(static)))

    sections, dynamic = [], []
    base = 0
    for i in range(shards):
        n_i = entries // shards + (1 if i < entries % shards else 0)
        _, t_i, d_i = _layer_sizes(n_i, f_s, f_t)
        sec = {}
        for t, c in exact_shares(t_i, distinct).items():
            lru = c - int(round(f_ts * c))
            if lru > 0:
                sec[t] = (base, max(lru // ways, 1))
                base += sec[t][1]
        sections.append(sec)
        nd = max(d_i // ways, 1) if d_i > 0 else 0
        dynamic.append((base, nd))
        base += nd
    return Layout(static_hashes, shards, ways, sections, dynamic, base)


def set_of(layout: Layout, keys: np.ndarray, key_topic: np.ndarray):
    """(static mask, global set index) of every request."""
    h = splitmix64(keys)
    static = np.isin(h, layout.static_hashes)
    shard = ((h >> np.uint64(32)) % np.uint64(layout.shards)).astype(np.int64)
    lo = (h & np.uint64(0xFFFFFFFF)).astype(np.int64)
    topic = np.asarray(key_topic, np.int64)[np.asarray(keys, np.int64)]
    first = np.empty(len(keys), np.int64)
    count = np.empty(len(keys), np.int64)
    for i in range(layout.shards):
        on = shard == i
        f, c = layout.dynamic[i]
        first[on], count[on] = f, c
        for t, (f, c) in layout.sections[i].items():
            sel = on & (topic == t)
            first[sel], count[sel] = f, c
    return static, first + lo % np.maximum(count, 1)


def replay(
    layout: Layout, keys: np.ndarray, key_topic: np.ndarray,
    batch_sizes: np.ndarray, refresh: bool = True,
) -> np.ndarray:
    """Hit mask of ``keys`` served in consecutive batches of
    ``batch_sizes`` from an empty cache."""
    keys = np.asarray(keys, np.int64)
    static, sets_idx = set_of(layout, keys, key_topic)
    hits = static.copy()
    live = np.flatnonzero(~static)
    bounds = np.searchsorted(live, np.cumsum(np.concatenate([[0], batch_sizes])))
    k_l = keys[live].tolist()
    s_l = sets_idx[live].tolist()
    resident = bytearray(len(live))
    ways = layout.ways
    table: List[list] = [[] for _ in range(layout.n_sets)]
    b = bounds.tolist()
    for a, z in zip(b[:-1], b[1:]):
        for j in range(a, z):  # atomic probe: the state at batch start
            if k_l[j] in table[s_l[j]]:
                resident[j] = 1
        for j in range(a, z):  # then the commit, in arrival order
            lst = table[s_l[j]]
            k = k_l[j]
            if k in lst:
                if refresh and lst[-1] != k:
                    lst.remove(k)
                    lst.append(k)
            else:
                if len(lst) >= ways:
                    del lst[0]
                lst.append(k)
    hits[live] = np.frombuffer(bytes(resident), np.uint8).astype(bool)
    return hits


def expected_values(keys: np.ndarray, rec_keys: np.ndarray, rec_rows: np.ndarray):
    """The backend's answer for each of ``keys``, from the recorded
    backend calls (the first answer recorded for a query), and whether
    one was recorded at all."""
    if not len(rec_keys):
        return np.zeros((len(keys), rec_rows.shape[1]), rec_rows.dtype), np.zeros(len(keys), bool)
    order = np.argsort(rec_keys, kind="stable")
    uq, first = np.unique(rec_keys[order], return_index=True)
    rows = rec_rows[order][first]
    pos = np.minimum(np.searchsorted(uq, keys), len(uq) - 1)
    return rows[pos], uq[pos] == keys
