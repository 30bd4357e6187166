"""Seeded query streams for the benchmark.

A copy of the calibrated stream generator (``generate``) and the
piecewise-stationary drift generator (``generate_drifting``) of the
program's query-log module, so that the benchmark's traffic cannot move
when the program's generator is edited.  Same seed, same stream:
``tests/chipbench/test_chipbench_traffic.py`` holds the copy to the
original at a small size.  Only the benchmark's ``--seed`` drives them.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import numpy as np

#: topic id of a query that belongs to no topic
NO_TOPIC = -1


@dataclass
class SynthConfig:
    n_requests: int = 2_000_000
    n_topics: int = 96
    #: distinct topical queries (split across topics by Zipf shares)
    n_topical_queries: int = 300_000
    #: distinct non-singleton no-topic queries
    n_notopic_queries: int = 120_000
    #: fraction of requests that belong to some topic
    topical_fraction: float = 0.62
    #: of the no-topic requests, fraction that are fresh singletons
    singleton_fraction: float = 0.35
    #: Zipf exponent for query popularity inside a topic / the no-topic pool
    zipf_query: float = 1.05
    #: Zipf exponent for topic popularity
    zipf_topic: float = 0.85
    #: daily-cycle modulation amplitude per topic, drawn U[0, amp_max]
    amp_max: float = 0.9
    #: simulated duration in days (drives the periodic modulation)
    n_days: float = 21.0
    #: time buckets with piecewise-constant topic intensities
    n_buckets: int = 2048
    #: per-topic daily active-window length in days (~hours of burst)
    window_frac: float = 0.15
    #: background (out-of-window) topic intensity relative to in-window
    off_intensity: float = 0.3
    #: decouple topic *traffic* share from topic *diversity* (distinct-query
    #: count): the paper's proportional allocation wins exactly when these
    #: differ (banking: low traffic, many distinct bank-name queries)
    decouple_diversity: bool = True
    #: fraction of a topic's pool forming its stable "core" (recurring
    #: queries: "first bank", "texas state bank", ... in the paper's
    #: miss analysis); the rest is a high-churn tail
    core_frac: float = 0.06
    #: probability that a topical request targets the core
    p_core: float = 0.75
    #: Zipf exponent inside the core (flat: individually unpopular)
    zipf_core: float = 0.3
    #: daily core churn: fraction of core slots rotated into the tail
    core_churn: float = 0.0
    #: vocabulary for clicked-document text
    vocab_size: int = 4096
    doc_len: Tuple[int, int] = (30, 80)
    #: per-topic word-distribution concentration (small = peaked topics)
    topic_dirichlet: float = 0.04
    #: background-word mixture weight inside a document
    background_mix: float = 0.2
    seed: int = 0


@dataclass
class SynthLog:
    """Generated log.  Key ids are dense in [0, n_queries)."""

    keys: np.ndarray  # (n,) int64 request stream
    timestamps: np.ndarray  # (n,) float64 days since epoch, ascending
    true_topic: np.ndarray  # (n_queries,) ground-truth topic or NO_TOPIC
    n_terms: np.ndarray  # (n_queries,) query length in words
    n_chars: np.ndarray  # (n_queries,) query length in characters
    #: clicked-document tokens per *topical* query id (None for no-click)
    docs: Dict[int, np.ndarray] = field(default_factory=dict)
    #: click count per query id (voting weight)
    clicks: Optional[np.ndarray] = None
    #: the generator's topic-word distributions (diagnostics only)
    phi: Optional[np.ndarray] = None
    config: Optional[SynthConfig] = None

    @property
    def n_queries(self) -> int:
        return len(self.true_topic)

    def split(self, train_frac: float) -> int:
        """Index splitting the stream into train/test by time order."""
        return int(len(self.keys) * train_frac)


def _zipf_pmf(n: int, s: float) -> np.ndarray:
    ranks = np.arange(1, n + 1, dtype=np.float64)
    p = ranks ** (-s)
    return p / p.sum()


def _sample_zipf(rng, n_draws: int, n_items: int, s: float) -> np.ndarray:
    """Inverse-CDF Zipf sampling (exact, vectorized)."""
    cdf = np.cumsum(_zipf_pmf(n_items, s))
    u = rng.random(n_draws)
    return np.searchsorted(cdf, u, side="right").astype(np.int64)


def generate(cfg: SynthConfig) -> SynthLog:
    rng = np.random.default_rng(cfg.seed)
    k = cfg.n_topics
    n = cfg.n_requests

    # ----- topic universe ---------------------------------------------------
    topic_share = _zipf_pmf(k, cfg.zipf_topic)
    # distinct queries per topic: diversity is decoupled from traffic (a
    # low-traffic topic can have a large distinct-query universe) -- the
    # structural asymmetry proportional allocation exploits.
    diversity = _zipf_pmf(k, cfg.zipf_topic).copy()
    if cfg.decouple_diversity:
        rng.shuffle(diversity)
    m_topic = np.maximum(
        32, np.round(diversity * cfg.n_topical_queries).astype(np.int64)
    )
    topic_offset = np.concatenate([[0], np.cumsum(m_topic)])
    n_topical = int(topic_offset[-1])
    n_nt = cfg.n_notopic_queries

    # ----- temporal topic intensities (piecewise-constant over buckets) ----
    # Each topic is "hot" during a daily window at a topic-specific hour
    # (weather in the morning, sports at the weekend, paper Sec. 1), with a
    # weekly modulation; outside its window it trickles at off_intensity.
    b = cfg.n_buckets
    t_day = np.linspace(0, cfg.n_days, b, endpoint=False)
    phase_day = rng.random(k)  # window center, in fraction of a day
    phase_week = rng.random(k) * 2 * np.pi
    amp_week = rng.random(k) * cfg.amp_max * 0.6
    frac = t_day[:, None] - np.floor(t_day[:, None])  # time of day in [0,1)
    dist = np.abs(frac - phase_day[None, :])
    dist = np.minimum(dist, 1.0 - dist)  # circular distance to window center
    in_window = dist < (cfg.window_frac / 2)
    gate = np.where(in_window, 1.0, cfg.off_intensity)
    weekly = 1 + amp_week[None, :] * np.cos(2 * np.pi * t_day[:, None] / 7.0 - phase_week)
    inten = topic_share[None, :] * gate * np.maximum(weekly, 0.1)
    inten = np.maximum(inten, 1e-9)
    inten /= inten.sum(axis=1, keepdims=True)

    # ----- per-request layout ----------------------------------------------
    is_topical = rng.random(n) < cfg.topical_fraction
    bucket = np.minimum((np.arange(n) * b) // n, b - 1)
    keys = np.empty(n, dtype=np.int64)

    # topical requests: choose topic by bucket intensity, query by Zipf
    top_pos = np.flatnonzero(is_topical)
    # Per-bucket multinomial topic counts (piecewise-constant intensities);
    # within a bucket the topic order is shuffled -- locality is preserved
    # at bucket granularity (~minutes of simulated time).
    topics_of_pos = np.empty(len(top_pos), dtype=np.int64)
    bucket_of_top = bucket[top_pos]  # non-decreasing
    bounds = np.searchsorted(bucket_of_top, np.arange(b + 1))
    for bb in range(b):
        lo, hi = bounds[bb], bounds[bb + 1]
        if hi == lo:
            continue
        counts = rng.multinomial(hi - lo, inten[bb])
        block = np.repeat(np.arange(k), counts)
        rng.shuffle(block)
        topics_of_pos[lo:hi] = block
    # Query choice inside a topic: a stable flat-ish CORE of recurring,
    # individually-unpopular queries (the paper's "first bank" / "texas
    # state bank" miss analysis) plus a high-churn Zipf TAIL that drives
    # the topic's distinct-query count.  Core membership rotates slowly
    # (daily churn), so a frozen static cache goes stale while a per-topic
    # LRU adapts -- the temporal-locality signature of Sec. 1 / Fig. 6.
    n_days_i = int(np.ceil(cfg.n_days))
    day_of_pos = np.minimum(
        (np.arange(n, dtype=np.int64) * n_days_i) // n, n_days_i - 1
    )
    for t in range(k):
        sel = np.flatnonzero(topics_of_pos == t)
        if len(sel) == 0:
            continue
        m_t = int(m_topic[t])
        c_t = max(4, int(round(cfg.core_frac * m_t)))
        n_churn = int(round(cfg.core_churn * c_t))
        # per-day core: stable block [0, c_t) with n_churn slots rotating
        # through the tail region
        cores = np.tile(np.arange(c_t, dtype=np.int64), (n_days_i, 1))
        if n_churn and m_t > c_t:
            for dd in range(n_days_i):
                cores[dd, c_t - n_churn :] = c_t + (
                    (dd * n_churn + np.arange(n_churn)) % (m_t - c_t)
                )
        is_core = rng.random(len(sel)) < cfg.p_core
        days = day_of_pos[top_pos[sel]]
        qid = np.empty(len(sel), dtype=np.int64)
        n_core_req = int(is_core.sum())
        if n_core_req:
            ranks = _sample_zipf(rng, n_core_req, c_t, cfg.zipf_core)
            qid[is_core] = cores[days[is_core], ranks]
        n_tail_req = len(sel) - n_core_req
        if n_tail_req:
            if m_t > c_t:
                tail_ranks = _sample_zipf(rng, n_tail_req, m_t - c_t, cfg.zipf_query)
                qid[~is_core] = c_t + tail_ranks
            else:
                qid[~is_core] = _sample_zipf(rng, n_tail_req, m_t, cfg.zipf_query)
        keys[top_pos[sel]] = topic_offset[t] + qid

    # no-topic requests: Zipf pool + singleton tail
    nt_pos = np.flatnonzero(~is_topical)
    is_single = rng.random(len(nt_pos)) < cfg.singleton_fraction
    pool = _sample_zipf(rng, int((~is_single).sum()), n_nt, cfg.zipf_query)
    keys[nt_pos[~is_single]] = n_topical + pool
    n_singles = int(is_single.sum())
    keys[nt_pos[is_single]] = n_topical + n_nt + np.arange(n_singles)

    n_queries = n_topical + n_nt + n_singles

    # ----- ground-truth topics ---------------------------------------------
    true_topic = np.full(n_queries, NO_TOPIC, dtype=np.int64)
    for t in range(k):
        true_topic[topic_offset[t] : topic_offset[t + 1]] = t

    # ----- query surface features (admission policy) -----------------------
    # popular queries are short; rare/singleton queries long (paper Sec. 5).
    # Calibrated so the Baeza-Yates thresholds (Y=5 terms, Z=20 chars)
    # reject mostly the rare tail, not the reusable head.
    freq = np.bincount(keys, minlength=n_queries)
    log_rarity = np.log1p(1.0 / np.maximum(freq, 1))
    n_terms = 1 + rng.poisson(0.25 + 0.8 * log_rarity)
    n_chars = (n_terms * (3 + rng.poisson(1.5, size=n_queries)) + 2).astype(np.int64)

    # ----- clicked-document text (LDA training substrate) ------------------
    v = cfg.vocab_size
    phi = rng.dirichlet(np.full(v, cfg.topic_dirichlet), size=k)  # (k, v)
    background = _zipf_pmf(v, 1.0)
    rng.shuffle(background)
    docs: Dict[int, np.ndarray] = {}
    # Only *requested* topical queries get docs (a click requires a request),
    # and a small fraction have no click at all (paper: removed from LDA).
    requested = np.flatnonzero(freq > 0)
    topical_req = requested[true_topic[requested] != NO_TOPIC]
    has_click = rng.random(len(topical_req)) > 0.08
    clicked = topical_req[has_click]
    lens = rng.integers(cfg.doc_len[0], cfg.doc_len[1], size=len(clicked))
    # Vectorized per-topic sampling: inverse-CDF draws grouped by topic.
    phi_cdf = np.cumsum(phi, axis=1)
    bg_cdf = np.cumsum(background)
    starts = np.concatenate([[0], np.cumsum(lens)])
    total = int(starts[-1])
    words_all = np.empty(total, dtype=np.int32)
    tok_topic = np.repeat(true_topic[clicked], lens)
    u = rng.random(total)
    for t in np.unique(tok_topic):
        sel = tok_topic == t
        words_all[sel] = np.searchsorted(phi_cdf[t], u[sel], side="right")
    mix = rng.random(total) < cfg.background_mix
    words_all[mix] = np.searchsorted(bg_cdf, rng.random(int(mix.sum())), side="right")
    np.clip(words_all, 0, v - 1, out=words_all)
    for i, qid in enumerate(clicked):
        docs[int(qid)] = words_all[starts[i] : starts[i + 1]]
    clicks = np.maximum(1, (freq * rng.beta(2, 5, size=n_queries))).astype(np.int64)

    timestamps = np.linspace(0, cfg.n_days, n)
    return SynthLog(
        keys=keys,
        timestamps=timestamps,
        true_topic=true_topic,
        n_terms=n_terms.astype(np.int64),
        n_chars=n_chars,
        docs=docs,
        clicks=clicks,
        phi=phi,
        config=cfg,
    )


# ---------------------------------------------------------------------------
# Time-varying popularity streams (popularity drift; Gao et al.)
# ---------------------------------------------------------------------------


@dataclass
class DriftConfig:
    """Piecewise-stationary topic popularity with drifting query mixtures.

    The stream is split into ``n_phases`` equal segments.  Within a phase
    everything is stationary; at each phase boundary the *topic*
    popularity ranking is re-drawn (a seeded permutation of the same Zipf
    shares -- yesterday's cold topic becomes today's hot one) and, with
    ``rotate_queries``, the *within-topic* Zipf head rotates through the
    topic's query pool (a drifting mixture of Zipf sources in the style
    of Gao et al.'s time-varying popularity model).  A cache allocation
    frozen on the first phase's statistics is therefore honestly stale
    for every later phase -- the scenario the drift rebalancer exists
    for, and the one ``benchmarks/fig_drift.py`` measures.

    Queries are dense ids: topic ``t`` owns ``[t*m, (t+1)*m)`` with
    ``m = queries_per_topic``; the stationary no-topic pool follows.
    """

    n_requests: int = 400_000
    n_topics: int = 24
    queries_per_topic: int = 1_500
    n_notopic_queries: int = 5_000
    topical_fraction: float = 0.85
    #: Zipf exponent over topic popularity ranks (per phase)
    zipf_topic: float = 1.1
    #: Zipf exponent over query ranks inside a topic (flat-ish: capacity,
    #: not a tiny hot head, is what buys hits)
    zipf_query: float = 0.7
    #: popularity phases; 1 = stationary (no drift)
    n_phases: int = 4
    #: rotate each topic's Zipf head at every phase boundary
    rotate_queries: bool = True
    #: of the no-topic requests, fraction that are fresh singletons --
    #: churn that pollutes a global LRU but never reaches the topic
    #: partitions (the isolation the paper's topic layer buys)
    singleton_fraction: float = 0.0
    seed: int = 0


def generate_drifting(cfg: DriftConfig) -> SynthLog:
    """Generate a piecewise-stationary drift stream (see ``DriftConfig``)."""
    rng = np.random.default_rng(cfg.seed)
    k, n, m = cfg.n_topics, cfg.n_requests, cfg.queries_per_topic
    phases = max(1, int(cfg.n_phases))
    base = _zipf_pmf(k, cfg.zipf_topic)
    # phase 0 keeps the identity ranking; later phases permute it
    perms = [np.arange(k)] + [rng.permutation(k) for _ in range(phases - 1)]
    phase_of = np.minimum((np.arange(n) * phases) // n, phases - 1)

    is_topical = rng.random(n) < cfg.topical_fraction
    keys = np.empty(n, dtype=np.int64)
    top_pos = np.flatnonzero(is_topical)
    q_cdf = np.cumsum(_zipf_pmf(m, cfg.zipf_query))
    for p in range(phases):
        sel = top_pos[phase_of[top_pos] == p]
        if not len(sel):
            continue
        share = np.empty(k)
        share[perms[p]] = base  # perms[p][j] is phase p's rank-j topic
        topic = rng.choice(k, size=len(sel), p=share)
        rank = np.searchsorted(q_cdf, rng.random(len(sel)), side="right")
        rank = np.minimum(rank, m - 1)
        if cfg.rotate_queries:
            # shift which queries form the Zipf head: same pool, new hot set
            rank = (rank + (p * m) // phases) % m
        keys[sel] = topic * m + rank

    nt_pos = np.flatnonzero(~is_topical)
    n_topical = k * m
    is_single = rng.random(len(nt_pos)) < cfg.singleton_fraction
    pool_pos = nt_pos[~is_single]
    if len(pool_pos):
        keys[pool_pos] = n_topical + _sample_zipf(
            rng, len(pool_pos), cfg.n_notopic_queries, 1.0
        )
    sing_pos = nt_pos[is_single]
    keys[sing_pos] = n_topical + cfg.n_notopic_queries + np.arange(len(sing_pos))
    n_queries = n_topical + cfg.n_notopic_queries + len(sing_pos)

    true_topic = np.full(n_queries, NO_TOPIC, dtype=np.int64)
    true_topic[:n_topical] = np.repeat(np.arange(k, dtype=np.int64), m)

    # surface features: enough for the admission policies to be applicable
    freq = np.bincount(keys, minlength=n_queries)
    n_terms = 1 + rng.poisson(0.5 + 0.6 * np.log1p(1.0 / np.maximum(freq, 1)))
    n_chars = (n_terms * 5 + 2).astype(np.int64)

    return SynthLog(
        keys=keys,
        timestamps=np.linspace(0, float(phases), n),  # one "day" per phase
        true_topic=true_topic,
        n_terms=n_terms.astype(np.int64),
        n_chars=n_chars,
        docs={},
        clicks=None,
        phi=None,
        config=None,
    )
