"""Composable decoder-only transformer covering the assigned LM family.

One implementation, config-switched:

* GQA / MQA grouped attention (gemma-2b is MQA: kv=1)
* RoPE positions
* gated activations (GeGLU for gemma, SwiGLU for glm4/llama4/arctic)
* local<->global alternating attention with sliding window (gemma2)
* attention & final logit soft-capping (gemma2)
* dropless MoE via sort + ``jax.lax.ragged_dot`` (llama4-scout top-1,
  arctic top-2), optionally with a parallel dense residual FFN (arctic)
* tied or untied embeddings

Layers are stacked on a leading axis and executed with ``lax.scan`` (+
optional remat) to keep HLO size and compile time flat in depth.  Query
chunking keeps the attention working set far below the naive (S, S)
materialization.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .common import apply_rope, cross_entropy, dense, init_rmsnorm, rmsnorm, softcap, truncated_normal

Params = Any


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int  # per-expert hidden size
    #: arctic-style dense FFN residual computed in parallel with the MoE
    dense_residual_ff: int = 0
    router_aux_weight: float = 0.01
    #: expert GEMM implementation: "capacity" scans experts with a fixed
    #: per-expert token budget (GShard-style drops; memory-flat on every
    #: backend); "ragged" uses jax.lax.ragged_dot (dropless, efficient on
    #: TPU Mosaic, but its reference lowering materializes a dense
    #: (tokens, experts, ff) intermediate)
    impl: str = "capacity"
    capacity_factor: float = 1.25


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    activation: str = "silu"  # gate activation: "silu" (SwiGLU) | "gelu" (GeGLU)
    rope_theta: float = 10_000.0
    #: "global" or "local_global" (even layers local / odd global, gemma2)
    attn_pattern: str = "global"
    window: int = 4096
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    #: overrides the default head_dim**-0.5 attention scale (gemma2 uses
    #: (d_model/n_heads)**-0.5 even though head_dim differs)
    query_scale: Optional[float] = None
    qkv_bias: bool = False
    post_norms: bool = False  # gemma2 post-attention/post-ffw norms
    embed_scale: bool = False  # gemma multiplies embeddings by sqrt(d)
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    #: distribution of the MoE layer, set by the launcher: token batch is
    #: processed shard-locally (local top-k + local sort + ragged GEMMs)
    #: and the expert FFN is tensor-parallel over ``moe_tp_axis`` with one
    #: psum -- a GLOBAL argsort would force GSPMD to replicate the token
    #: stream (observed: 31 TB/device on arctic-480b train).
    moe_batch_axes: Optional[Tuple[str, ...]] = None
    moe_tp_axis: Optional[str] = None
    #: axes over which the expert dimension FSDP-shards at rest (a suffix
    #: of moe_batch_axes whose product divides n_experts)
    moe_fsdp_axes: Tuple[str, ...] = ()
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    #: query chunk for memory-bounded attention (None = unchunked)
    q_chunk: Optional[int] = 1024
    remat: bool = True
    #: lax.scan over the layer stack (compile time / HLO size flat in L).
    #: False unrolls a python loop -- used by the dry-run's delta-L cost
    #: probes, because XLA's cost analysis counts a scan body ONCE
    #: regardless of trip count.
    scan_layers: bool = True
    #: perf lever (train): shard the residual stream's sequence axis over
    #: this mesh axis between layers ("sequence parallelism") -- the remat
    #: carries shrink by the axis size at the cost of per-layer gathers
    act_seq_axis: Optional[str] = None
    #: perf lever (decode): local layers slice a window-sized view of the
    #: KV cache instead of reading (and masking) the whole buffer;
    #: requires scan_layers=False (the slice shape is layer-dependent)
    decode_window_slice: bool = False
    #: perf lever (decode): int8 KV cache with per (layer, head) scales
    kv_quant: bool = False

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    def layer_is_local(self) -> np.ndarray:
        if self.attn_pattern == "local_global":
            return (np.arange(self.n_layers) % 2) == 0
        return np.zeros(self.n_layers, dtype=bool)

    def param_count(self) -> int:
        """Analytic parameter count (for roofline MODEL_FLOPS)."""
        d, hd = self.d_model, self.head_dim
        attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
        if self.moe is not None:
            ff = self.moe.n_experts * (3 * d * self.moe.d_ff) + d * self.moe.n_experts
            if self.moe.dense_residual_ff:
                ff += 3 * d * self.moe.dense_residual_ff
        else:
            ff = 3 * d * self.d_ff
        per_layer = attn + ff + 2 * d
        embed = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + embed

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: only routed experts)."""
        if self.moe is None:
            return self.param_count()
        d = self.d_model
        attn = d * self.head_dim * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * self.head_dim * d
        ff = self.moe.top_k * (3 * d * self.moe.d_ff) + d * self.moe.n_experts
        if self.moe.dense_residual_ff:
            ff += 3 * d * self.moe.dense_residual_ff
        per_layer = attn + ff + 2 * d
        embed = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + embed


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_layer(key, cfg: TransformerConfig) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {
        "attn": {
            "q": truncated_normal(ks[0], (d, cfg.n_heads * hd), d**-0.5, cfg.dtype),
            "k": truncated_normal(ks[1], (d, cfg.n_kv_heads * hd), d**-0.5, cfg.dtype),
            "v": truncated_normal(ks[2], (d, cfg.n_kv_heads * hd), d**-0.5, cfg.dtype),
            "o": truncated_normal(ks[3], (cfg.n_heads * hd, d), (cfg.n_heads * hd) ** -0.5, cfg.dtype),
        },
        "pre_attn_norm": init_rmsnorm(d, cfg.dtype),
        "pre_mlp_norm": init_rmsnorm(d, cfg.dtype),
    }
    if cfg.qkv_bias:
        p["attn"]["q_bias"] = jnp.zeros((cfg.n_heads * hd,), cfg.dtype)
        p["attn"]["k_bias"] = jnp.zeros((cfg.n_kv_heads * hd,), cfg.dtype)
        p["attn"]["v_bias"] = jnp.zeros((cfg.n_kv_heads * hd,), cfg.dtype)
    if cfg.post_norms:
        p["post_attn_norm"] = init_rmsnorm(d, cfg.dtype)
        p["post_mlp_norm"] = init_rmsnorm(d, cfg.dtype)
    if cfg.moe is not None:
        m = cfg.moe
        # wi is (E, D, 2, F) -- gate/up on a dedicated axis so that F can be
        # tensor-parallel sharded without splitting across the gate boundary
        p["moe"] = {
            "router": truncated_normal(ks[4], (d, m.n_experts), d**-0.5, jnp.float32),
            "wi": truncated_normal(ks[5], (m.n_experts, d, 2, m.d_ff), d**-0.5, cfg.dtype),
            "wo": truncated_normal(ks[6], (m.n_experts, m.d_ff, d), m.d_ff**-0.5, cfg.dtype),
        }
        if m.dense_residual_ff:
            p["mlp"] = {
                "wi": truncated_normal(ks[7], (d, 2 * m.dense_residual_ff), d**-0.5, cfg.dtype),
                "wo": truncated_normal(ks[7], (m.dense_residual_ff, d), m.dense_residual_ff**-0.5, cfg.dtype),
            }
    else:
        p["mlp"] = {
            "wi": truncated_normal(ks[5], (d, 2 * cfg.d_ff), d**-0.5, cfg.dtype),
            "wo": truncated_normal(ks[6], (cfg.d_ff, d), cfg.d_ff**-0.5, cfg.dtype),
        }
    return p


def init_params(key, cfg: TransformerConfig) -> Params:
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    layers = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    params: Dict[str, Any] = {
        "embed": truncated_normal(k_embed, (cfg.vocab_size, cfg.d_model), 1.0, cfg.dtype),
        "layers": layers,
        "final_norm": init_rmsnorm(cfg.d_model, cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = truncated_normal(
            k_head, (cfg.d_model, cfg.vocab_size), cfg.d_model**-0.5, cfg.dtype
        )
    return params


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _attention_scores(q, k, cfg: TransformerConfig, q_pos, k_pos, is_local):
    """q: (B, Sq, Nkv, G, hd); k: (B, Sk, Nkv, hd) -> weights (B,Sq,Nkv,G,Sk)."""
    scale = cfg.query_scale if cfg.query_scale is not None else cfg.head_dim**-0.5
    logits = jnp.einsum("bqngh,bknh->bqngk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    logits = softcap(logits, cfg.attn_logit_softcap)
    causal = k_pos[None, :] <= q_pos[:, None]  # (Sq, Sk)
    in_window = k_pos[None, :] > (q_pos[:, None] - cfg.window)
    mask = jnp.where(is_local, causal & in_window, causal)
    logits = jnp.where(mask[None, :, None, None, :], logits, -1e30)
    return jax.nn.softmax(logits, axis=-1)


def _attend(q, k, v, cfg: TransformerConfig, q_pos, k_pos, is_local):
    """Query-chunked attention. Shapes as in _attention_scores; v like k."""
    b, sq = q.shape[0], q.shape[1]
    chunk = cfg.q_chunk
    if chunk is None or sq <= chunk or sq % chunk != 0:
        w = _attention_scores(q, k, cfg, q_pos, k_pos, is_local)
        return jnp.einsum("bqngk,bknh->bqngh", w, v).astype(q.dtype)

    n_chunks = sq // chunk
    qc = q.reshape(b, n_chunks, chunk, *q.shape[2:])
    pc = q_pos.reshape(n_chunks, chunk)

    def one(args):
        qi, pi = args
        w = _attention_scores(qi, k, cfg, pi, k_pos, is_local)
        return jnp.einsum("bqngk,bknh->bqngh", w, v).astype(q.dtype)

    out = jax.lax.map(one, (jnp.moveaxis(qc, 1, 0), pc))
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, *q.shape[2:])


def _qkv(layer: Params, x: jnp.ndarray, cfg: TransformerConfig, positions):
    b, s, _ = x.shape
    a = layer["attn"]
    q = dense({"w": a["q"]}, x)
    k = dense({"w": a["k"]}, x)
    v = dense({"w": a["v"]}, x)
    if cfg.qkv_bias:
        q = q + a["q_bias"].astype(q.dtype)
        k = k + a["k_bias"].astype(k.dtype)
        v = v + a["v_bias"].astype(v.dtype)
    q = q.reshape(b, s, cfg.n_kv_heads, cfg.q_per_kv, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q.reshape(b, s, -1, cfg.head_dim), positions, cfg.rope_theta).reshape(q.shape)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# FFN / MoE
# ---------------------------------------------------------------------------


def _act(cfg: TransformerConfig, gate: jnp.ndarray) -> jnp.ndarray:
    if cfg.activation == "gelu":
        return jax.nn.gelu(gate, approximate=True)
    return jax.nn.silu(gate)


def _dense_ffn(mlp: Params, x: jnp.ndarray, cfg: TransformerConfig) -> jnp.ndarray:
    h = dense({"w": mlp["wi"]}, x)
    gate, up = jnp.split(h, 2, axis=-1)
    return dense({"w": mlp["wo"]}, _act(cfg, gate) * up)


def _moe_local(x: jnp.ndarray, router, wi, wo, cfg: TransformerConfig, tp_axis: Optional[str]):
    """Shard-local dropless MoE body.

    x: (T_local, D); wi: (E, D, 2, F_local); wo: (E, F_local, D).  Routing,
    top-k and the token sort are local to the shard; the expert FFN is
    tensor-parallel over ``tp_axis`` (F sharded), closed by one psum.
    """
    m = cfg.moe
    logits = (x.astype(jnp.float32) @ router.astype(jnp.float32))  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, m.top_k)  # (T, k)
    weights = weights / jnp.maximum(weights.sum(axis=-1, keepdims=True), 1e-9)

    t = x.shape[0]
    flat_expert = experts.reshape(-1)  # (T*k,) expert id per slot
    order = jnp.argsort(flat_expert)  # stable
    tok_of_slot = order // m.top_k  # originating token per sorted slot
    xs = jnp.take(x, tok_of_slot, axis=0)  # (T*k, D)
    group_sizes = jnp.bincount(flat_expert, length=m.n_experts).astype(jnp.int32)

    e, d, _, f = wi.shape
    if m.impl == "ragged":
        h = jax.lax.ragged_dot(
            xs, wi.reshape(e, d, 2 * f).astype(x.dtype), group_sizes
        )  # (T*k, 2*F_local)
        gate = h[:, :f]
        up = h[:, f:]
        h = _act(cfg, gate) * up
        y = jax.lax.ragged_dot(h, wo.astype(x.dtype), group_sizes)  # (T*k, D)
    else:
        y = _capacity_grouped_ffn(xs, wi, wo, group_sizes, cfg)
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)

    # Un-sort and combine with routing weights.
    unsorted = jnp.zeros_like(y).at[order].set(y)
    out = (unsorted.reshape(t, m.top_k, -1) * weights[..., None].astype(y.dtype)).sum(axis=1)

    # Switch-style load-balance aux: E * sum_e fraction_e * prob_e.
    frac = jnp.mean(jax.nn.one_hot(experts[:, 0], m.n_experts, dtype=jnp.float32), axis=0)
    pmean = probs.mean(axis=0)
    aux = m.n_experts * jnp.sum(frac * pmean)
    return out.astype(x.dtype), aux


def _capacity_grouped_ffn(
    xs: jnp.ndarray,  # (T*k, D) tokens sorted by expert
    wi: jnp.ndarray,  # (E, D, 2, F)
    wo: jnp.ndarray,  # (E, F, D)
    group_sizes: jnp.ndarray,  # (E,)
    cfg: TransformerConfig,
) -> jnp.ndarray:
    """Grouped GEMM with a static per-expert capacity.

    Scans experts; each step dynamic-slices a capacity-sized window at its
    group's start, computes the FFN, masks tokens beyond the group size and
    *accumulates* back (windows of neighbouring groups may overlap, and a
    group larger than the capacity drops its tail -- GShard semantics).
    Peak memory is one (C, 2F) activation regardless of backend.
    """
    m = cfg.moe
    tk, d = xs.shape
    e, _, _, f = wi.shape
    cap = int(np.ceil(m.capacity_factor * tk / e / 8)) * 8
    cap = min(max(cap, 8), tk)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes)[:-1].astype(jnp.int32)]
    )

    def step(out, inp):
        wi_e, wo_e, start, size = inp
        start = jnp.minimum(start, tk - cap)  # keep the window in bounds
        x_e = jax.lax.dynamic_slice(xs, (start, 0), (cap, d))
        h = jnp.einsum("cd,dgf->cgf", x_e, wi_e.astype(x_e.dtype))
        h = _act(cfg, h[:, 0]) * h[:, 1]  # (C, F)
        y = h @ wo_e.astype(h.dtype)  # (C, D)
        # valid = token belongs to this expert's group (not padding overlap
        # from the clamp above, not beyond the group size)
        pos = start + jax.lax.broadcasted_iota(jnp.int32, (cap, 1), 0)[:, 0]
        grp_start = inp[2]
        valid = (pos >= grp_start) & (pos < grp_start + size)
        y = jnp.where(valid[:, None], y, 0.0)
        region = jax.lax.dynamic_slice(out, (start, 0), (cap, d))
        out = jax.lax.dynamic_update_slice(out, region + y, (start, 0))
        return out, None

    out0 = jnp.zeros_like(xs)
    out, _ = jax.lax.scan(step, out0, (wi, wo, starts, group_sizes.astype(jnp.int32)))
    return out


def _moe_ffn(moe_p: Params, x: jnp.ndarray, cfg: TransformerConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dropless MoE dispatch: shard_map'd when the launcher set axes."""
    if cfg.moe_batch_axes is None:
        # single-shard path: wi reshaped (E, D, 2, F) -> dense local compute
        return _moe_local(x, moe_p["router"], moe_p["wi"], moe_p["wo"], cfg, None)

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = get_moe_mesh()
    batch = cfg.moe_batch_axes if len(cfg.moe_batch_axes) > 1 else cfg.moe_batch_axes[0]
    tp = cfg.moe_tp_axis

    fsdp = cfg.moe_fsdp_axes
    fsdp_spec = fsdp if len(fsdp) > 1 else (fsdp[0] if fsdp else None)

    def body(xl, router, wi, wo):
        # FSDP on the expert axis: weights rest sharded over (a suffix of)
        # the batch axes and gathered transiently per layer; the transpose
        # of the gather is the grads' reduce-scatter.
        if fsdp:
            wi = jax.lax.all_gather(wi, fsdp, axis=0, tiled=True)
            wo = jax.lax.all_gather(wo, fsdp, axis=0, tiled=True)
        out, aux = _moe_local(xl, router, wi, wo, cfg, tp)
        aux = jax.lax.pmean(aux, cfg.moe_batch_axes)
        if tp is not None:
            aux = jax.lax.pmean(aux, tp)
        return out, aux

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(batch, None),
            P(),
            P(fsdp_spec, None, None, tp),
            P(fsdp_spec, tp, None),
        ),
        out_specs=(P(batch, None), P()),
        check_vma=False,
    )
    # pad tokens to the shard count (decode at tiny batch): padded zero
    # tokens route like any token and are sliced away after
    t = x.shape[0]
    n_shards = 1
    for a in cfg.moe_batch_axes:
        n_shards *= mesh.shape[a]
    pad = (-t) % n_shards
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out, aux = fn(x, moe_p["router"], moe_p["wi"], moe_p["wo"])
    return out[:t], aux


# Trace-time mesh handle for the shard_map'd MoE and the activation
# sharding constraints (set by the launcher; analogous to flax's mesh
# context).
_MOE_MESH = None


def set_moe_mesh(mesh) -> None:
    global _MOE_MESH
    _MOE_MESH = mesh


# alias: the mesh context is used by more than the MoE now
set_mesh = set_moe_mesh


def get_moe_mesh():
    if _MOE_MESH is None:
        raise RuntimeError("set_moe_mesh(mesh) must be called before tracing a "
                           "distributed MoE step")
    return _MOE_MESH


def _constrain_residual(x: jnp.ndarray, cfg: TransformerConfig) -> jnp.ndarray:
    """Sequence-parallel residual stream: (B, S, D) sharded on S between
    layers.  Cuts the remat-saved carries by the axis size; attention and
    FFN re-gather internally (GSPMD inserts the collectives)."""
    if cfg.act_seq_axis is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = get_moe_mesh()
    batch = cfg.moe_batch_axes or ()
    bspec = batch if len(batch) > 1 else (batch[0] if batch else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(bspec, cfg.act_seq_axis, None))
    )


# ---------------------------------------------------------------------------
# Layer / model forward
# ---------------------------------------------------------------------------


def layer_forward(
    layer: Params,
    x: jnp.ndarray,
    cfg: TransformerConfig,
    positions: jnp.ndarray,
    is_local,
    k_cache: Optional[jnp.ndarray] = None,
    v_cache: Optional[jnp.ndarray] = None,
    cache_len: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[Tuple[jnp.ndarray, jnp.ndarray]]]:
    """One decoder layer.  In decode mode (caches given), x is (B, 1, D) and
    new K/V are written at ``cache_len``.  Returns (x, aux, new_cache)."""
    b, s, _ = x.shape
    h = rmsnorm(layer["pre_attn_norm"], x, cfg.norm_eps)
    q, k, v = _qkv(layer, h, cfg, positions)

    if k_cache is not None:
        # decode: append to cache, attend over the buffer (masked)
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, cache_len, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, cache_len, axis=1)
        if cfg.decode_window_slice and isinstance(is_local, (bool, np.bool_)) and is_local:
            # perf lever: a local layer only ever attends inside its
            # window -- slice it instead of streaming the whole cache.
            w = min(cfg.window, k_cache.shape[1])
            start = jnp.clip(cache_len - (w - 1), 0, k_cache.shape[1] - w)
            k_full = jax.lax.dynamic_slice_in_dim(k_cache, start, w, axis=1)
            v_full = jax.lax.dynamic_slice_in_dim(v_cache, start, w, axis=1)
            k_pos = start + jnp.arange(w)
            valid = k_pos <= cache_len
            # window condition holds by construction of the slice
            attn = _attend_decode(q, k_full, v_full, cfg, positions, k_pos, valid, False)
        else:
            k_full, v_full = k_cache, v_cache
            k_pos = jnp.arange(k_cache.shape[1])
            # mask out unwritten future slots
            valid = k_pos <= cache_len
            attn = _attend_decode(q, k_full, v_full, cfg, positions, k_pos, valid, is_local)
        new_cache = (k_cache, v_cache)
    else:
        k_pos = positions
        attn = _attend(q, k, v, cfg, positions, k_pos, is_local)
        new_cache = None

    attn = attn.reshape(b, s, cfg.n_heads * cfg.head_dim)
    attn = dense({"w": layer["attn"]["o"]}, attn)
    if cfg.post_norms:
        attn = rmsnorm(layer["post_attn_norm"], attn, cfg.norm_eps)
    x = x + attn

    h = rmsnorm(layer["pre_mlp_norm"], x, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe is not None:
        flat = h.reshape(b * s, -1)
        y, aux = _moe_ffn(layer["moe"], flat, cfg)
        y = y.reshape(b, s, -1)
        if cfg.moe.dense_residual_ff:
            y = y + _dense_ffn(layer["mlp"], h, cfg)
    else:
        y = _dense_ffn(layer["mlp"], h, cfg)
    if cfg.post_norms:
        y = rmsnorm(layer["post_mlp_norm"], y, cfg.norm_eps)
    return x + y, aux, new_cache


def _attend_decode(q, k, v, cfg, q_pos, k_pos, valid, is_local):
    """Decode attention over the full cache buffer with validity mask."""
    scale = cfg.query_scale if cfg.query_scale is not None else cfg.head_dim**-0.5
    logits = jnp.einsum("bqngh,bknh->bqngk", q, k, preferred_element_type=jnp.float32) * scale
    logits = softcap(logits, cfg.attn_logit_softcap)
    causal = k_pos[None, :] <= q_pos[:, None]
    in_window = k_pos[None, :] > (q_pos[:, None] - cfg.window)
    mask = jnp.where(is_local, causal & in_window, causal) & valid[None, :]
    logits = jnp.where(mask[None, :, None, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bqngk,bknh->bqngh", w, v).astype(q.dtype)


def _embed(params: Params, tokens: jnp.ndarray, cfg: TransformerConfig) -> jnp.ndarray:
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), cfg.dtype)
    return x


def _unembed(params: Params, x: jnp.ndarray, cfg: TransformerConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype))
    else:
        logits = x @ params["lm_head"].astype(x.dtype)
    return softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)


def _scan_layers(body, x0, xs_tree, cfg: TransformerConfig):
    """lax.scan over stacked layers, or an unrolled python loop."""
    if cfg.scan_layers:
        return jax.lax.scan(body, x0, xs_tree)
    carry = x0
    outs = []
    for i in range(cfg.n_layers):
        sl = jax.tree.map(lambda a: a[i], xs_tree)
        carry, out = body(carry, sl)
        outs.append(out)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    return carry, stacked


def forward(params: Params, tokens: jnp.ndarray, cfg: TransformerConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Training/prefill forward: tokens (B, S) -> (logits (B,S,V) f32, aux)."""
    b, s = tokens.shape
    x = _embed(params, tokens, cfg)
    positions = jnp.arange(s)
    locals_ = jnp.asarray(cfg.layer_is_local())

    def body(x, scanned):
        layer, is_local = scanned
        x = _constrain_residual(x, cfg)
        x, aux, _ = layer_forward(layer, x, cfg, positions, is_local)
        x = _constrain_residual(x, cfg)
        return x, aux

    if cfg.remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, auxes = _scan_layers(body, x, (params["layers"], locals_), cfg)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return _unembed(params, x, cfg), auxes.mean()


def loss_fn(params: Params, batch: Dict[str, jnp.ndarray], cfg: TransformerConfig) -> jnp.ndarray:
    logits, aux = forward(params, batch["tokens"], cfg)
    loss = cross_entropy(logits[:, :-1], batch["tokens"][:, 1:])
    if cfg.moe is not None:
        loss = loss + cfg.moe.router_aux_weight * aux
    return loss


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode with KV cache
# ---------------------------------------------------------------------------


def init_cache(cfg: TransformerConfig, batch: int, max_len: int) -> Dict[str, jnp.ndarray]:
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def decode_step(
    params: Params,
    cache: Dict[str, jnp.ndarray],
    tokens: jnp.ndarray,  # (B, 1)
    cfg: TransformerConfig,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One decode step: append token, attend over cache, return logits."""
    b = tokens.shape[0]
    cur = cache["len"]
    x = _embed(params, tokens, cfg)
    positions = jnp.full((1,), cur, dtype=jnp.int32)
    locals_ = jnp.asarray(cfg.layer_is_local())

    if cfg.scan_layers:
        def body(x, scanned):
            layer, is_local, k_c, v_c = scanned
            x, _, (k_new, v_new) = layer_forward(
                layer, x, cfg, positions, is_local, k_cache=k_c, v_cache=v_c, cache_len=cur
            )
            return x, (k_new, v_new)

        x, (k_all, v_all) = jax.lax.scan(
            body, x, (params["layers"], locals_, cache["k"], cache["v"])
        )
    else:
        # unrolled: is_local becomes a python bool, enabling the
        # structurally-different windowed read on local layers
        loc = cfg.layer_is_local()
        ks, vs = [], []
        for i in range(cfg.n_layers):
            layer = jax.tree.map(lambda a: a[i], params["layers"])
            x, _, (k_new, v_new) = layer_forward(
                layer, x, cfg, positions, bool(loc[i]),
                k_cache=cache["k"][i], v_cache=cache["v"][i], cache_len=cur,
            )
            ks.append(k_new)
            vs.append(v_new)
        k_all, v_all = jnp.stack(ks), jnp.stack(vs)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _unembed(params, x, cfg)
    new_cache = {"k": k_all, "v": v_all, "len": cur + 1}
    return logits[:, 0], new_cache


def prefill(
    params: Params,
    tokens: jnp.ndarray,  # (B, S)
    cfg: TransformerConfig,
    max_len: Optional[int] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Process a full prompt, building the KV cache."""
    b, s = tokens.shape
    max_len = max_len or s
    x = _embed(params, tokens, cfg)
    positions = jnp.arange(s)
    locals_ = jnp.asarray(cfg.layer_is_local())

    def body(x, scanned):
        layer, is_local = scanned
        h = rmsnorm(layer["pre_attn_norm"], x, cfg.norm_eps)
        q, k, v = _qkv(layer, h, cfg, positions)
        attn = _attend(q, k, v, cfg, positions, positions, is_local)
        attn = attn.reshape(b, s, cfg.n_heads * cfg.head_dim)
        attn = dense({"w": layer["attn"]["o"]}, attn)
        if cfg.post_norms:
            attn = rmsnorm(layer["post_attn_norm"], attn, cfg.norm_eps)
        x = x + attn
        h2 = rmsnorm(layer["pre_mlp_norm"], x, cfg.norm_eps)
        if cfg.moe is not None:
            y, _ = _moe_ffn(layer["moe"], h2.reshape(b * s, -1), cfg)
            y = y.reshape(b, s, -1)
            if cfg.moe.dense_residual_ff:
                y = y + _dense_ffn(layer["mlp"], h2, cfg)
        else:
            y = _dense_ffn(layer["mlp"], h2, cfg)
        if cfg.post_norms:
            y = rmsnorm(layer["post_mlp_norm"], y, cfg.norm_eps)
        x = x + y
        pad = max_len - s
        k_buf = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_buf = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x, (k_buf, v_buf)

    if cfg.remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, (k_all, v_all) = _scan_layers(body, x, (params["layers"], locals_), cfg)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _unembed(params, x[:, -1:], cfg)
    cache = {"k": k_all, "v": v_all, "len": jnp.asarray(s, jnp.int32)}
    return logits[:, 0], cache
