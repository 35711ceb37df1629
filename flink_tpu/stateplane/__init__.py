"""flink_tpu.stateplane — the shared state-plane kernel library.

One home for the compiled device programs every engine dispatches
(ROADMAP item 5): the canonical flat program families
(:mod:`~flink_tpu.stateplane.families`), the pane-ring delta-harvest
bundle (:mod:`~flink_tpu.stateplane.pane`), the exchange-rank
combinator with its first Pallas backend
(:mod:`~flink_tpu.stateplane.rank`), and the pluggable per-family
backend hook (:mod:`~flink_tpu.stateplane.backends`). Engines —
SlotTable, PaneTable, the mesh engines, the joins — are thin policies
over these builders; flint REG04 pins every PROGRAM_CACHE kind to
:data:`KNOWN_PROGRAM_FAMILIES`.
"""

from flink_tpu.stateplane.backends import (
    backend_of,
    backend_scope,
    configure_backends,
    set_backend,
)
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
    build_exchange_rank,
    exchange_rank_flat,
    pallas_rank,
    xla_rank,
)

__all__ = [
    "KNOWN_PROGRAM_FAMILIES",
    "backend_of",
    "backend_scope",
    "build_exchange_rank",
    "configure_backends",
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
    "pallas_rank",
    "pane_programs",
    "set_backend",
    "xla_rank",
]
