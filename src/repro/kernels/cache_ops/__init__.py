from .kernel import PAD_HI, PAD_LO
from .ops import (
    PACKED_WORDS,
    fill_winner_slots,
    pack_words,
    plan_segments,
    probe_and_commit_op,
    replay_lane_rounds,
    replay_schedule,
    resolve_conflicts,
    serve_fused_op,
    unpack_epoch,
    unpack_words,
)
from .ref import probe_and_commit_ref, serve_fused_ref

__all__ = [
    "PACKED_WORDS",
    "PAD_HI",
    "PAD_LO",
    "fill_winner_slots",
    "pack_words",
    "plan_segments",
    "probe_and_commit_op",
    "probe_and_commit_ref",
    "replay_lane_rounds",
    "replay_schedule",
    "resolve_conflicts",
    "serve_fused_op",
    "serve_fused_ref",
    "unpack_epoch",
    "unpack_words",
]
