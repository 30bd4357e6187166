"""Public op: conflict-aware fused probe-and-commit with kernel dispatch.

The sequential `STDDeviceCache.commit` replays a batch one request at a
time (O(B) device steps).  This op reproduces its semantics bit-exactly
with three data-parallel phases:

1. **plan** -- stable-sort the batch by set index; each run of equal sets
   is a *segment* whose requests must apply in arrival order;
2. **resolve** -- gather one packed row of key/stamp words per segment
   and replay round j = 0, 1, ... across *all* segments at once: round j
   applies every segment's j-th request.  The loop runs max-segment-length
   times, not B times;
3. **scatter** -- write each resolved row back in a single scatter.

State layout: the per-slot key_hi / key_lo / stamp / epoch words live in
one packed ``(S, 4W)`` uint32 array (``pack_words`` / ``unpack_words``:
columns ``[0:W]`` hi, ``[W:2W]`` lo, ``[2W:3W]`` stamp bit-cast,
``[3W:4W]`` insertion epoch), so the resolve phase costs **one** gather
and **one** scatter instead of four of each, and the Pallas kernel's row
blocks fill 4x more of the 128-wide lanes.  The adapters are exact
bit-reinterpretations, which is what lets the fori_loop oracle keep
operating on the unpacked view.

Freshness rides the same gather: per request, ``min_epoch`` is the
smallest insertion epoch still considered fresh (0 disables expiry --
the default -- making the op bit-identical to the pre-freshness
semantics), and ``epochs`` is the insertion epoch stamped on writes.  A
key match whose epoch is below ``min_epoch`` is *stale*: it still counts
as a hit for LRU/eviction purposes (the entry stays resident and the
matched way refreshes), but the op reports it in ``pre_stale`` and
schedules a value refresh (``wrote``) so callers can either re-fetch
(``stale_policy=miss``) or serve stale while the deferred fill
revalidates.  See docs/freshness.md.

`use_kernel=True` routes phase 2 through the Pallas kernel (interpret=True
on CPU hosts); otherwise a pure-jnp implementation of the same rounds loop
runs (the broker's default on CPU).  Values never enter the op: an
admitted miss's result only exists after the backend replies, so the op
reports per-request write slots (`wrote`, `way`) and callers apply the
deferred value fill (``STDDeviceCache.fill_values``) -- last insert per
slot wins, exactly the order the sequential commit writes them.

Requests carrying the reserved pad key (packed hash ``(PAD_HI,
PAD_LO)``) are inert in every engine: never a hit, never admitted, never
an eviction -- shape-bucketed serving pads ragged batches with them.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..interpret import resolve_interpret
from .kernel import PAD_HI, PAD_LO, conflict_round, is_pad
from .kernel import probe_and_commit as _kernel_call
from .ref import probe_and_commit_ref  # noqa: F401  (re-exported for tests)
from .ref import serve_fused_ref  # noqa: F401  (re-exported for tests)
from .serve_kernel import serve_fused as _serve_kernel_call

Array = Union[np.ndarray, jnp.ndarray]

#: words packed per cache slot: key_hi, key_lo, stamp, insertion epoch
PACKED_WORDS = 4


def pack_words(key_hi: Array, key_lo: Array, stamp: Array, epoch: Array = None) -> Array:
    """Pack per-slot (key_hi, key_lo, stamp[, epoch]) into one ``(..., 4W)``
    uint32 array -- the device state's lane-friendly layout.  The stamp
    words are bit-reinterpreted (int32 -> uint32), so pack/unpack is
    exact.  ``epoch`` defaults to zeros (entries inserted before the
    freshness subsystem existed, or with it disabled, carry epoch 0)."""
    if isinstance(key_hi, np.ndarray):
        if epoch is None:
            epoch = np.zeros(key_hi.shape, np.uint32)
        return np.concatenate(
            [
                np.asarray(key_hi, np.uint32),
                np.asarray(key_lo, np.uint32),
                np.ascontiguousarray(np.asarray(stamp, np.int32)).view(np.uint32),
                np.asarray(epoch, np.uint32),
            ],
            axis=-1,
        )
    if epoch is None:
        epoch = jnp.zeros(key_hi.shape, jnp.uint32)
    return jnp.concatenate(
        [
            key_hi.astype(jnp.uint32),
            key_lo.astype(jnp.uint32),
            stamp.astype(jnp.uint32),
            epoch.astype(jnp.uint32),
        ],
        axis=-1,
    )


def unpack_words(ks: Array) -> Tuple[Array, Array, Array]:
    """``(..., 4W)`` packed words -> (key_hi, key_lo, stamp) views.

    For numpy inputs the three outputs are *views* into ``ks`` (the host
    engine mutates them in place); for jnp inputs they are slices of the
    same buffer (XLA fuses the split into the consumer).  The epoch word
    has its own accessor (``unpack_epoch``) so pre-freshness callers keep
    their three-tuple destructuring.
    """
    w = ks.shape[-1] // PACKED_WORDS
    hi = ks[..., :w]
    lo = ks[..., w : 2 * w]
    st = ks[..., 2 * w : 3 * w]
    if isinstance(ks, np.ndarray):
        return hi, lo, st.view(np.int32)
    return hi, lo, st.astype(jnp.int32)


def unpack_epoch(ks: Array) -> Array:
    """``(..., 4W)`` packed words -> the insertion-epoch word (uint32).

    A numpy input yields a mutable view (host engine); jnp a slice."""
    w = ks.shape[-1] // PACKED_WORDS
    return ks[..., 3 * w :]


def plan_segments(
    set_idx: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Describe the per-set conflict structure of a batch.

    Returns ``(order, seg_id, leader, seg_len, seg_set)``: a stable
    sort permutation grouping equal sets while preserving arrival order,
    the segment id of each sorted item, and per-segment (padded to B with
    ``leader == B`` / ``seg_len == 0``) first-item index, length and set.
    """
    b = set_idx.shape[0]
    order = jnp.argsort(set_idx)  # jnp.argsort is stable: ties keep arrival order
    sset = set_idx[order]
    start = jnp.concatenate([jnp.ones((1,), bool), sset[1:] != sset[:-1]])
    seg_id = jnp.cumsum(start) - 1
    arange = jnp.arange(b, dtype=jnp.int32)
    leader = jnp.full((b,), b, jnp.int32).at[seg_id].min(arange)
    seg_len = jnp.zeros((b,), jnp.int32).at[seg_id].add(1)
    seg_set = sset[jnp.minimum(leader, b - 1)]  # padded slots repeat the last set
    return order, seg_id, leader, seg_len, seg_set


def resolve_conflicts(
    rows_hi: jnp.ndarray,  # (B, W) one pristine row per segment
    rows_lo: jnp.ndarray,
    rows_st: jnp.ndarray,
    rows_ep: jnp.ndarray,  # (B, W) uint32 insertion epochs
    s_hi: jnp.ndarray,  # (B,) sorted request fields
    s_lo: jnp.ndarray,
    s_pos: jnp.ndarray,  # original batch positions (stamps follow arrival)
    s_admit: jnp.ndarray,
    s_static: jnp.ndarray,
    s_epoch: jnp.ndarray,  # (B,) uint32 insertion epoch stamped on writes
    s_minep: jnp.ndarray,  # (B,) uint32 freshness floor (0 = no expiry)
    leader: jnp.ndarray,
    seg_len: jnp.ndarray,
    clock: jnp.ndarray,
    seg_id: jnp.ndarray = None,  # (B,) sorted-position -> segment (optional)
):
    """Pure-jnp rounds loop: replay round j across all segments at once.

    Bit-exact with the sequential fori_loop commit: within a segment the
    evolving row sees exactly the same match / argmin-eviction / stamp /
    staleness sequence, and segments never share a set so rounds are
    independent.

    The loop carries only what actually evolves: the packed rows plus the
    write plan (``wrote``/``way``) -- and the loop body is scatter-free.
    The probe outputs (``pre_hit``/``pre_way``/``pre_stale``/``pre_epoch``)
    are pure functions of the *pristine* rows, so
    :func:`probe_and_commit_op` computes them in one vectorized pass; and
    each sorted position is written in exactly one round (its rank within
    its segment), so the write plan lands through a per-segment gather
    masked by rank instead of a per-round scatter.  On XLA CPU scatters
    price at ~170ns/index, which made the per-query cost *flat* in batch
    size (~2 scatters x rounds each) and kept B=4096 exactly as slow per
    query as B=256 -- the ``cache_commit_vec_xla`` anomaly; gathers are an
    order of magnitude cheaper and let large batches amortize.

    ``seg_id`` (from :func:`plan_segments`) enables the gather-based plan;
    when omitted it is recomputed from ``leader``/``seg_len``.
    """
    b = rows_hi.shape[0]
    if seg_id is None:
        # positions covered by segment s are [leader[s], leader[s]+len[s])
        starts = jnp.zeros(b + 1, jnp.int32).at[jnp.minimum(leader, b)].add(
            jnp.where(seg_len > 0, 1, 0), mode="drop"
        )
        seg_id = jnp.cumsum(starts[:b]) - 1
    rank = jnp.arange(b, dtype=jnp.int32) - leader[seg_id]

    def body(j, carry):
        r_hi, r_lo, r_st, r_ep, wr, wy = carry
        idx = jnp.minimum(leader + j, b - 1)
        act = j < seg_len
        r_hi, r_lo, r_st, r_ep, is_hit, way, do_write, refresh = conflict_round(
            r_hi, r_lo, r_st, r_ep, s_hi[idx], s_lo[idx], s_admit[idx],
            s_static[idx], s_epoch[idx], s_minep[idx],
            clock + 1 + s_pos[idx], act,
        )
        # position p's plan was computed this round iff its in-segment
        # rank is j: select it from its segment's lane (gather + where,
        # no scatter)
        sel = rank == j
        wr = jnp.where(sel, refresh[seg_id], wr)
        wy = jnp.where(sel, way[seg_id], wy)
        return r_hi, r_lo, r_st, r_ep, wr, wy

    init = (
        rows_hi,
        rows_lo,
        rows_st,
        rows_ep,
        jnp.zeros(b, bool),
        jnp.zeros(b, jnp.int32),
    )
    return jax.lax.fori_loop(0, jnp.max(seg_len), body, init)


def _pad(x: jnp.ndarray, target: int, value=0):
    if x.shape[0] == target:
        return x
    pad = [(0, target - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad, constant_values=value)


def probe_and_commit_op(
    ks: jnp.ndarray,  # (S, 4W) uint32 packed key/stamp/epoch state
    h_hi: jnp.ndarray,  # (B,) uint32 request hashes
    h_lo: jnp.ndarray,
    set_idx: jnp.ndarray,  # (B,) int32
    admit: jnp.ndarray,  # (B,) bool
    static_hit: jnp.ndarray,  # (B,) bool (static-layer hits never write)
    clock: jnp.ndarray,  # () int32
    epochs: jnp.ndarray = None,  # (B,) uint32 write epochs (None -> 0)
    min_epoch: jnp.ndarray = None,  # (B,) uint32 freshness floor (None -> 0)
    use_kernel: bool = False,
    interpret: Optional[bool] = None,
    bm: int = 256,
) -> Dict[str, jnp.ndarray]:
    """Fused probe + batch commit over the packed state array.

    Returns the updated ``ks`` plus, per request (original batch order):
    ``pre_hit``/``pre_way``/``pre_stale``/``pre_epoch`` -- the probe
    outcome against pre-commit state (``pre_stale``: matched, but the
    entry's epoch is below the request's ``min_epoch`` floor), and
    ``wrote``/``way`` -- the deferred value fill plan (inserts *and*
    stale refreshes).  The caller owns the clock bump and value scatter.
    With ``min_epoch`` unset or zero nothing ever expires and the op is
    bit-identical to the pre-freshness semantics.
    """
    b = h_hi.shape[0]
    if epochs is None:
        epochs = jnp.zeros((b,), jnp.uint32)
    if min_epoch is None:
        min_epoch = jnp.zeros((b,), jnp.uint32)
    if b == 0:
        z = jnp.zeros((0,), jnp.int32)
        zb = jnp.zeros((0,), bool)
        return dict(
            ks=ks,
            pre_hit=zb, pre_way=z,
            pre_stale=zb, pre_epoch=jnp.zeros((0,), jnp.uint32),
            wrote=jnp.zeros((0,), bool), way=z,
        )
    order, seg_id, leader, seg_len, seg_set = plan_segments(set_idx)
    rows = ks[seg_set]  # ONE gather: key + stamp + epoch words together
    rows_hi, rows_lo, rows_st = unpack_words(rows)
    rows_ep = unpack_epoch(rows)
    s_hi, s_lo = h_hi[order], h_lo[order]
    s_pos = order.astype(jnp.int32)
    s_admit, s_static = admit[order], static_hit[order]
    s_epoch = epochs[order].astype(jnp.uint32)
    s_minep = min_epoch[order].astype(jnp.uint32)
    # Effective write epoch: a pristine *fresh* hit keeps its resident
    # epoch.  A mid-batch conflict can evict such an entry and re-insert
    # it in a later round (the caller serves and re-fills its probed,
    # unchanged value -- no backend dispatch happens for it), so stamping
    # the request epoch there would launder the entry's age.  Dispatched
    # data (true misses, stale refreshes) stamps the request epoch.  The
    # rule is idempotent, and with all-zero epochs it writes zero either
    # way, so pre-freshness behavior is bit-identical.
    s_rows = rows[seg_id]
    sr_hi, sr_lo, _ = unpack_words(s_rows)
    sr_ep = unpack_epoch(s_rows)
    s_pm = (sr_hi == s_hi[:, None]) & (sr_lo == s_lo[:, None]) & (sr_hi != 0)
    s_pm = s_pm & ~is_pad(s_hi, s_lo)[:, None]
    s_pm_ep = jnp.where(s_pm, sr_ep, 0).max(axis=1)
    s_epoch = jnp.where(s_pm.any(axis=1) & (s_pm_ep >= s_minep), s_pm_ep, s_epoch)

    if use_kernel:
        bp = ((b + bm - 1) // bm) * bm if b > bm else b
        col = lambda x: _pad(x, bp)[:, None]
        r_rows, p_hit, p_way, p_stale, p_ep, wr, wy = _kernel_call(
            _pad(rows, bp),
            col(leader),
            col(seg_len),
            col(s_hi),
            col(s_lo),
            col(s_pos),
            col(s_admit.astype(jnp.int32)),
            col(s_static.astype(jnp.int32)),
            col(s_epoch),
            col(s_minep),
            jnp.reshape(clock.astype(jnp.int32), (1, 1)),
            bm=bm,
            interpret=resolve_interpret(interpret),
        )
        r_rows = r_rows[:b]
        p_hit = p_hit[:b, 0] != 0
        p_way = p_way[:b, 0]
        p_stale = p_stale[:b, 0] != 0
        p_ep = p_ep[:b, 0]
        wr = wr[:b, 0] != 0
        wy = wy[:b, 0]
    else:
        r_hi, r_lo, r_st, r_ep, wr, wy = resolve_conflicts(
            rows_hi, rows_lo, rows_st, rows_ep, s_hi, s_lo, s_pos,
            s_admit, s_static, s_epoch, s_minep, leader, seg_len, clock,
            seg_id=seg_id,
        )
        r_rows = pack_words(r_hi, r_lo, r_st, r_ep)
        # probe outputs are pure functions of the pristine per-item rows
        # (already gathered for the effective-epoch fold above): one
        # vectorized pass, no per-round scatters
        p_hit = s_pm.any(axis=1)
        p_way = jnp.argmax(s_pm, axis=1).astype(jnp.int32)
        p_stale = p_hit & (s_pm_ep < s_minep)
        p_ep = s_pm_ep

    # ONE scatter of the resolved packed rows; padded segments drop
    scat = jnp.where(leader < b, seg_set, ks.shape[0])
    new_ks = ks.at[scat].set(r_rows, mode="drop")

    # un-sort via one inverse permutation (a single index scatter) + cheap
    # gathers, instead of one scatter per output array -- XLA CPU prices
    # scatters ~10x above gathers, and six per call was most of what kept
    # the vec_xla engine's per-query cost flat in batch size
    inv = jnp.zeros(b, jnp.int32).at[order].set(jnp.arange(b, dtype=jnp.int32))

    def unsort(x):
        return x[inv]

    return dict(
        ks=new_ks,
        pre_hit=unsort(p_hit),
        pre_way=unsort(p_way),
        pre_stale=unsort(p_stale),
        pre_epoch=unsort(p_ep),
        wrote=unsort(wr),
        way=unsort(wy),
    )


def fill_winner_slots(
    nslots: int,
    w: int,
    f_set_idx: jnp.ndarray,  # (F,) int32 deferred-fill set indices
    f_wrote: jnp.ndarray,  # (F,) bool
    f_way: jnp.ndarray,  # (F,) int32
) -> jnp.ndarray:
    """Deduplicate a deferred-fill plan to unique last-writer slots.

    Returns per plan entry the flat value-table slot ``set * W + way`` it
    may scatter into, or ``nslots`` (one past the end -- ``mode="drop"``
    discards it) for entries that did not write, lost a slot collision to
    a later writer, or point out of bounds.  Resolving collisions *before*
    the scatter makes the kernel's fill order-independent: every surviving
    index is unique, so XLA's unspecified duplicate-scatter order can
    never pick a different winner than the sequential commit would.
    """
    f = f_set_idx.shape[0]
    slot = jnp.where(
        f_wrote & (f_set_idx * w + f_way < nslots), f_set_idx * w + f_way, nslots
    )
    pos = jnp.arange(f, dtype=jnp.int32)
    last = jnp.full((nslots,), -1, jnp.int32).at[slot].max(pos, mode="drop")
    winner = f_wrote & (last[jnp.minimum(slot, nslots - 1)] == pos)
    return jnp.where(winner, slot, nslots).astype(jnp.int32)


def serve_fused_op(
    ks: jnp.ndarray,  # (S, 4W) uint32 packed key/stamp/epoch state
    value: jnp.ndarray,  # (S, W, V) int32 value table
    h_hi: jnp.ndarray,  # (B,) uint32 request hashes
    h_lo: jnp.ndarray,
    set_idx: jnp.ndarray,  # (B,) int32
    admit: jnp.ndarray,  # (B,) bool
    static_hit: jnp.ndarray,  # (B,) bool (static-layer hits never write)
    clock: jnp.ndarray,  # () int32
    f_set_idx: jnp.ndarray = None,  # (B,) deferred-fill plan (None -> empty)
    f_wrote: jnp.ndarray = None,
    f_way: jnp.ndarray = None,
    f_values: jnp.ndarray = None,  # (B, V)
    epochs: jnp.ndarray = None,  # (B,) uint32 write epochs (None -> 0)
    min_epoch: jnp.ndarray = None,  # (B,) uint32 freshness floor (None -> 0)
    use_kernel: bool = False,
    interpret: Optional[bool] = None,
    bm: int = 256,
) -> Dict[str, jnp.ndarray]:
    """One-dispatch serve: deferred-fill apply + fused probe/commit +
    probed value-row gather over the packed state and the value table.

    Everything :func:`probe_and_commit_op` returns, plus ``value`` (the
    post-fill value table -- the value-state update) and ``values`` (the
    per-request probed value rows, batch order; garbage on misses exactly
    like the standalone XLA gather).  The deferred-fill plan, when given,
    must be batch-length (callers pad; ``f_wrote == False`` entries are
    inert) and lands *before* the probe reads any value row.

    ``use_kernel=True`` routes the whole step through the fused Pallas
    serve kernel (one device dispatch; the interpreter on CPU hosts);
    otherwise the same phases run as jnp ops reusing
    :func:`probe_and_commit_op`, so the two paths -- and the sequential
    numpy oracle :func:`serve_fused_ref` -- are bit-exact by shared
    construction.
    """
    s, w, v = value.shape
    nslots = s * w
    b = h_hi.shape[0]
    if epochs is None:
        epochs = jnp.zeros((b,), jnp.uint32)
    if min_epoch is None:
        min_epoch = jnp.zeros((b,), jnp.uint32)
    if f_set_idx is None:
        f_slot = jnp.full((b,), nslots, jnp.int32)
        f_vals = jnp.zeros((b, v), value.dtype)
    else:
        f_slot = fill_winner_slots(
            nslots, w, f_set_idx.astype(jnp.int32), f_wrote, f_way.astype(jnp.int32)
        )
        f_vals = f_values
    if b == 0:
        z = jnp.zeros((0,), jnp.int32)
        zb = jnp.zeros((0,), bool)
        return dict(
            ks=ks, value=value, values=jnp.zeros((0, v), value.dtype),
            pre_hit=zb, pre_way=z,
            pre_stale=zb, pre_epoch=jnp.zeros((0,), jnp.uint32),
            wrote=zb, way=z,
        )

    if not use_kernel:
        flat = value.reshape(nslots, v)
        filled = flat.at[f_slot].set(f_vals, mode="drop").reshape(s, w, v)
        out = probe_and_commit_op(
            ks, h_hi, h_lo, set_idx, admit, static_hit, clock,
            epochs=epochs, min_epoch=min_epoch, use_kernel=False,
        )
        vals = filled[jnp.minimum(set_idx, s - 1), out["pre_way"]]
        return dict(out, value=filled, values=vals)

    order, seg_id, leader, seg_len, seg_set = plan_segments(set_idx)
    rows = ks[seg_set]  # ONE gather: key + stamp + epoch words together
    s_hi, s_lo = h_hi[order], h_lo[order]
    s_pos = order.astype(jnp.int32)
    s_admit, s_static = admit[order], static_hit[order]
    s_epoch = epochs[order].astype(jnp.uint32)
    s_minep = min_epoch[order].astype(jnp.uint32)
    # effective write epoch: same fold as probe_and_commit_op (a pristine
    # fresh hit keeps its resident epoch so a mid-batch evict + re-insert
    # cannot launder the entry's age)
    s_rows = rows[seg_id]
    sr_hi, sr_lo, _ = unpack_words(s_rows)
    sr_ep = unpack_epoch(s_rows)
    s_pm = (sr_hi == s_hi[:, None]) & (sr_lo == s_lo[:, None]) & (sr_hi != 0)
    s_pm = s_pm & ~is_pad(s_hi, s_lo)[:, None]
    s_pm_ep = jnp.where(s_pm, sr_ep, 0).max(axis=1)
    s_epoch = jnp.where(s_pm.any(axis=1) & (s_pm_ep >= s_minep), s_pm_ep, s_epoch)
    s_set = jnp.minimum(set_idx, s - 1).astype(jnp.int32)[order]

    bp = ((b + bm - 1) // bm) * bm if b > bm else b
    col = lambda x: _pad(x, bp)[:, None]
    r_rows, new_val, o_vals, p_hit, p_way, p_stale, p_ep, wr, wy = (
        _serve_kernel_call(
            _pad(rows, bp),
            col(leader),
            col(seg_len),
            col(s_hi),
            col(s_lo),
            col(s_pos),
            col(s_admit.astype(jnp.int32)),
            col(s_static.astype(jnp.int32)),
            col(s_epoch),
            col(s_minep),
            col(s_set),
            _pad(f_slot, bp, value=nslots)[:, None],  # padded plan drops
            _pad(f_vals, bp),
            value.reshape(nslots, v),
            jnp.reshape(clock.astype(jnp.int32), (1, 1)),
            bm=bm,
            interpret=resolve_interpret(interpret),
        )
    )
    r_rows = r_rows[:b]
    p_hit = p_hit[:b, 0] != 0
    p_way = p_way[:b, 0]
    p_stale = p_stale[:b, 0] != 0
    p_ep = p_ep[:b, 0]
    wr = wr[:b, 0] != 0
    wy = wy[:b, 0]

    # ONE scatter of the resolved packed rows; padded segments drop
    scat = jnp.where(leader < b, seg_set, ks.shape[0])
    new_ks = ks.at[scat].set(r_rows, mode="drop")

    def unsort(x):
        return jnp.zeros(x.shape, x.dtype).at[order].set(x)

    return dict(
        ks=new_ks,
        value=new_val.reshape(s, w, v),
        values=unsort(o_vals[:b]),
        pre_hit=unsort(p_hit),
        pre_way=unsort(p_way),
        pre_stale=unsort(p_stale),
        pre_epoch=unsort(p_ep),
        wrote=unsort(wr),
        way=unsort(wy),
    )
