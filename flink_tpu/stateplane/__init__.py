"""flink_tpu.stateplane — the shared state-plane kernel library.

One home for the compiled device programs every engine dispatches
(ROADMAP item 5): the canonical flat program families
(:mod:`~flink_tpu.stateplane.families`), the pane-ring delta-harvest
bundle (:mod:`~flink_tpu.stateplane.pane`) and the rank-within-
destination combinator the exchanges share
(:mod:`~flink_tpu.stateplane.rank`). Engines —
SlotTable, PaneTable, the mesh engines, the joins — are thin policies
over these builders; flint REG04 pins every PROGRAM_CACHE kind to
:data:`KNOWN_PROGRAM_FAMILIES`.
"""

from flink_tpu.stateplane.families import (
    KNOWN_PROGRAM_FAMILIES,
    flat_fence,
    flat_gather,
    flat_merge_pairs,
    flat_put,
    flat_reset,
    flat_scatter_combine,
    flat_scatter_signed,
    flat_scatter_valued,
    flat_segment_fire,
    flat_segment_fire_projected,
    flat_segment_merge,
)
from flink_tpu.stateplane.pane import pane_programs
from flink_tpu.stateplane.rank import (
    exchange_rank_flat,
    xla_rank,
)

__all__ = [
    "KNOWN_PROGRAM_FAMILIES",
    "exchange_rank_flat",
    "flat_fence",
    "flat_gather",
    "flat_merge_pairs",
    "flat_put",
    "flat_reset",
    "flat_scatter_combine",
    "flat_scatter_signed",
    "flat_scatter_valued",
    "flat_segment_fire",
    "flat_segment_fire_projected",
    "flat_segment_merge",
    "pane_programs",
    "xla_rank",
]
