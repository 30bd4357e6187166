"""PNA (Principal Neighbourhood Aggregation) GNN [arXiv:2004.05718].

Message passing is implemented with ``jax.ops.segment_sum`` / ``segment_max``
over an edge-index -> node scatter (JAX has no CSR SpMM; this IS the
system's sparse substrate).  PNA aggregates messages with
{mean, max, min, std} and rescales each by degree scalers
{identity, amplification, attenuation}, giving 12 concatenated views.

Shapes regimes (assigned):
* full-batch      : one graph, dense feature matrix + edge index
* sampled-training: mini-batch with a *real* fanout neighbor sampler
* batched-small   : (B, n_nodes, ...) padded molecules with masks
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .common import truncated_normal

Params = Any


@dataclasses.dataclass(frozen=True)
class PNAConfig:
    n_layers: int = 4
    d_in: int = 128
    d_hidden: int = 75
    n_classes: int = 40
    #: mean log-degree of the training graph (PNA's amplification scaler)
    delta: float = 2.5
    dtype: Any = jnp.float32

    @property
    def d_agg(self) -> int:
        return 4 * 3 * self.d_hidden  # aggregators x scalers x features


def init_params(key, cfg: PNAConfig) -> Params:
    ks = jax.random.split(key, cfg.n_layers * 2 + 2)
    layers = []
    d = cfg.d_hidden
    for i in range(cfg.n_layers):
        layers.append(
            {
                "msg": truncated_normal(ks[2 * i], (d, d), d**-0.5, cfg.dtype),
                "upd": truncated_normal(ks[2 * i + 1], (cfg.d_agg + d, d), (cfg.d_agg + d) ** -0.5, cfg.dtype),
            }
        )
    return {
        "encode": truncated_normal(ks[-2], (cfg.d_in, d), cfg.d_in**-0.5, cfg.dtype),
        "layers": layers,
        "decode": truncated_normal(ks[-1], (d, cfg.n_classes), d**-0.5, cfg.dtype),
    }


def _pna_aggregate(msgs: jnp.ndarray, dst: jnp.ndarray, n_nodes: int, delta: float) -> jnp.ndarray:
    """Messages (E, F) scattered to nodes: 4 aggregators x 3 degree scalers."""
    deg = jax.ops.segment_sum(jnp.ones_like(dst, dtype=msgs.dtype), dst, n_nodes)
    deg = jnp.maximum(deg, 1.0)[:, None]
    s = jax.ops.segment_sum(msgs, dst, n_nodes)
    mean = s / deg
    mx = jax.ops.segment_max(msgs, dst, n_nodes)
    mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
    mn = jax.ops.segment_min(msgs, dst, n_nodes)
    mn = jnp.where(jnp.isfinite(mn), mn, 0.0)
    sq = jax.ops.segment_sum(msgs * msgs, dst, n_nodes) / deg
    std = jnp.sqrt(jnp.maximum(sq - mean * mean, 1e-8))
    agg = jnp.concatenate([mean, mx, mn, std], axis=-1)  # (N, 4F)
    logd = jnp.log1p(deg)
    amp = logd / delta
    att = delta / jnp.maximum(logd, 1e-6)
    return jnp.concatenate([agg, agg * amp, agg * att], axis=-1)  # (N, 12F)


def forward(
    params: Params,
    x: jnp.ndarray,  # (N, d_in)
    edge_index: jnp.ndarray,  # (2, E) [src; dst]
    cfg: PNAConfig,
    node_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Full-graph / mini-batch-block forward -> node logits (N, n_classes)."""
    n = x.shape[0]
    src, dst = edge_index[0], edge_index[1]
    h = x @ params["encode"].astype(x.dtype)
    for layer in params["layers"]:
        msgs = jnp.take(h, src, axis=0) @ layer["msg"].astype(h.dtype)
        agg = _pna_aggregate(jax.nn.relu(msgs), dst, n, cfg.delta)
        h_new = jnp.concatenate([h, agg], axis=-1) @ layer["upd"].astype(h.dtype)
        h = h + jax.nn.relu(h_new)
    if node_mask is not None:
        h = h * node_mask[:, None].astype(h.dtype)
    return h @ params["decode"].astype(h.dtype)


def forward_batched(
    params: Params,
    x: jnp.ndarray,  # (B, N, d_in) padded molecules
    edge_index: jnp.ndarray,  # (B, 2, E) padded with E index n (self-loop sink)
    node_mask: jnp.ndarray,  # (B, N)
    cfg: PNAConfig,
) -> jnp.ndarray:
    """Batched small graphs -> per-graph logits via masked mean pooling."""
    per_graph = jax.vmap(lambda xi, ei, mi: forward(params, xi, ei, cfg, node_mask=mi))
    node_logits = per_graph(x, edge_index, node_mask)  # (B, N, C)
    denom = jnp.maximum(node_mask.sum(axis=1, keepdims=True), 1.0)
    return (node_logits * node_mask[..., None]).sum(axis=1) / denom


def loss_fn(params, batch, cfg: PNAConfig) -> jnp.ndarray:
    """Node-classification cross-entropy over (optionally masked) nodes."""
    logits = forward(params, batch["x"], batch["edge_index"], cfg)
    labels = batch["labels"]
    mask = batch.get("label_mask")
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()


# ---------------------------------------------------------------------------
# Distributed message passing (perf lever): destination-partitioned edges
# ---------------------------------------------------------------------------


def forward_dist(
    params: Params,
    x: jnp.ndarray,  # (N, d_in), N divisible by the shard count
    edge_index: jnp.ndarray,  # (2, E) GLOBAL node ids, E divisible; edges
    # pre-partitioned so each shard's slice holds edges whose dst is local
    cfg: PNAConfig,
    mesh,
    batch_axes,
) -> jnp.ndarray:
    """Vertex-cut PNA: shard nodes; each shard owns the edges pointing AT
    its nodes, so every segment reduction is shard-local.  The only
    collective is one all-gather of the (N, d_hidden) feature matrix per
    layer -- versus the baseline's all-reduce over the 12x-wider (N, d_agg)
    aggregate tensor that GSPMD emits for position-sharded edges.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = x.shape[0]
    axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    spec = axes if len(axes) > 1 else (axes[0] if axes else None)
    n_local = n // max(n_shards, 1)

    def body(x_l, ei_l):
        # shard-local ids: [0, n_local) real + sink row n_local for strays
        idx = jax.lax.axis_index(axes) if axes else 0
        off = idx * n_local
        src, dst = ei_l[0], ei_l[1]
        dst_local = dst - off
        in_shard = (dst_local >= 0) & (dst_local < n_local)
        dst_local = jnp.where(in_shard, dst_local, n_local)  # sink
        h_l = x_l @ params["encode"].astype(x_l.dtype)
        for layer in params["layers"]:
            h_full = (
                jax.lax.all_gather(h_l, axes, axis=0, tiled=True) if axes else h_l
            )
            msgs = jnp.take(h_full, src, axis=0) @ layer["msg"].astype(h_l.dtype)
            agg = _pna_aggregate(
                jax.nn.relu(msgs), dst_local, n_local + 1, cfg.delta
            )[:n_local]
            h_new = jnp.concatenate([h_l, agg], axis=-1) @ layer["upd"].astype(h_l.dtype)
            h_l = h_l + jax.nn.relu(h_new)
        return h_l @ params["decode"].astype(h_l.dtype)

    if not axes:
        return forward(params, x, edge_index, cfg)
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(spec, None), P(None, spec)),
        out_specs=P(spec, None),
        check_vma=False,
    )
    return fn(x, edge_index)


def partition_edges_by_dst(edge_index: np.ndarray, n_nodes: int, n_shards: int) -> np.ndarray:
    """Host-side layout contract for forward_dist: shard i's equal-sized
    slice holds exactly the edges whose dst lives in node block i, padded
    with sink edges (dst = -1, ignored by the kernel)."""
    dst = edge_index[1]
    n_local = max(n_nodes // n_shards, 1)
    shard = np.minimum(dst // n_local, n_shards - 1)
    counts = np.bincount(shard, minlength=n_shards)
    m = int(counts.max())
    out = np.zeros((2, n_shards * m), dtype=np.int64)
    out[1] = -1  # sink padding
    for s in range(n_shards):
        sel = np.flatnonzero(shard == s)
        out[:, s * m : s * m + len(sel)] = edge_index[:, sel]
    return out


# ---------------------------------------------------------------------------
# Neighbor sampler (host-side, numpy): fanout sampling for minibatch_lg
# ---------------------------------------------------------------------------


class NeighborSampler:
    """GraphSAGE-style fanout sampler over a CSR adjacency (host numpy)."""

    def __init__(self, n_nodes: int, edge_index: np.ndarray, seed: int = 0):
        src, dst = edge_index
        order = np.argsort(dst, kind="stable")
        self.nbr = src[order].astype(np.int64)
        counts = np.bincount(dst, minlength=n_nodes)
        self.offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        self.n_nodes = n_nodes
        self.rng = np.random.default_rng(seed)

    def sample_block(self, seeds: np.ndarray, fanouts: Tuple[int, ...]):
        """Returns (block_nodes, block_edge_index, seed_positions).

        ``block_nodes`` are original node ids (seeds first); the edge index
        is relabeled into block-local ids, deduplicated per hop.
        """
        nodes = list(seeds.astype(np.int64))
        pos = {int(v): i for i, v in enumerate(nodes)}
        edges_src: list = []
        edges_dst: list = []
        frontier = seeds.astype(np.int64)
        for f in fanouts:
            next_frontier = []
            for v in frontier:
                lo, hi = self.offsets[v], self.offsets[v + 1]
                if hi == lo:
                    continue
                deg = hi - lo
                take = min(f, int(deg))
                picks = self.nbr[lo + self.rng.choice(deg, size=take, replace=False)]
                for u in picks:
                    u = int(u)
                    if u not in pos:
                        pos[u] = len(nodes)
                        nodes.append(u)
                        next_frontier.append(u)
                    edges_src.append(pos[u])
                    edges_dst.append(pos[int(v)])
            frontier = np.asarray(next_frontier, dtype=np.int64)
        block_nodes = np.asarray(nodes, dtype=np.int64)
        ei = np.stack(
            [
                np.asarray(edges_src, dtype=np.int64),
                np.asarray(edges_dst, dtype=np.int64),
            ]
        ) if edges_src else np.zeros((2, 0), dtype=np.int64)
        return block_nodes, ei, np.arange(len(seeds))


def make_random_graph(
    n_nodes: int, n_edges: int, d_feat: int, n_classes: int, seed: int = 0,
    power_law: bool = True,
) -> Dict[str, np.ndarray]:
    """Synthetic graph with power-law degrees (benchmark substrate)."""
    rng = np.random.default_rng(seed)
    if power_law:
        w = rng.zipf(1.3, size=n_nodes).astype(np.float64)
        p = w / w.sum()
        src = rng.choice(n_nodes, size=n_edges, p=p)
    else:
        src = rng.integers(0, n_nodes, size=n_edges)
    dst = rng.integers(0, n_nodes, size=n_edges)
    x = rng.normal(size=(n_nodes, d_feat)).astype(np.float32)
    labels = rng.integers(0, n_classes, size=n_nodes)
    return {
        "x": x,
        "edge_index": np.stack([src, dst]).astype(np.int64),
        "labels": labels.astype(np.int64),
    }
