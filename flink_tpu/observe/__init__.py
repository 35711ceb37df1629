"""Runtime observability probes for the compiled hot path.

Static analysis (tools/flint) proves the *source* cannot host-sync or
destabilize jit identities; this package proves the *running program*
behaves: :mod:`~flink_tpu.observe.recompile_sentinel` counts actual XLA
backend compiles and device->host materializations around an engine
run and turns "the steady state recompiles" into an exception instead
of a silent 2-5x throughput loss, and
:mod:`~flink_tpu.observe.flight_recorder` is the always-on span plane
the whole batch lifecycle reports into (exported to Perfetto/Chrome
traces, Prometheus histograms and event-time latency markers by
:mod:`~flink_tpu.observe.export`).
"""

#: Canonical span-kind inventory — THE single source of truth shared by
#: the flight recorder (an unregistered kind raises at the call site),
#: the exporters (category mapping derives from this tuple) and flint's
#: REG03 registry check (tools/flint). Adding an instrumentation point
#: means adding its kind here; a typo in either direction — a call site
#: not listed, or a listed kind with no call site — fails both gates.
#: Keep this a plain literal tuple: flint parses it statically.
KNOWN_SPAN_KINDS = (
    # per-batch lifecycle (the engines' ingest -> emit pipeline)
    "batch.ingest",        # one engine process_batch (host prep + dispatch)
    "prep.meta_sweep",     # session-metadata absorb (native C or Python;
                           # work: sessions the sweep opened)
    "sweep.grouped",       # a batch the native sweep grouped by key in
                           # one hash pass, no sort: no key's timestamps
                           # stepped backwards in it (instant inside
                           # prep.meta_sweep; work: records)
    "session.merge",       # sessions whose accumulators a batch merged
                           # into another's: the merge kernel and the
                           # absorbed rows' free (work: sessions absorbed;
                           # 0 on an in-order stream)
    "prep.resolve",        # slice assignment + (key, slice) -> slot
                           # resolution on the host index (work: pairs
                           # newly given a slot)
    "resolve.sweep",       # a batch whose slice ends and slots one native
                           # sweep resolved (instant inside prep.resolve;
                           # work: records)
    "late.records",        # a batch that holds records behind a window
                           # that has fired, inside the allowed lateness
                           # (instant inside prep.resolve; work: the
                           # batch's records older than the newest fired
                           # window's end; absent from an in-order job)
    "prep.stage",          # input mapping, padding to the sticky bucket,
                           # shuffle staging into [P, B] blocks (work:
                           # bytes handed to the device, padding included)
    "device.dispatch",     # inline device interactions on the ingest path
    "device.fence_wait",   # host blocked on dispatch-ahead fences
    "exchange.stage1",     # two-level exchange: intra-host (ICI) route
    "exchange.stage2",     # two-level exchange: cross-host (DCN) hop +
                           # the stream-order scatter
    "fire.dispatch",       # watermark advance -> fire programs enqueued
    "fire.shard",          # one shard's fire-path host work (resolve,
                           # cold page extraction) — the per-shard track
                           # (work: (key, slice) cells the call resolved
                           # into the slot matrix: the slices that
                           # entered where the last window's matrix was
                           # carried on, every live cell where it was not)
    "carry.rows",          # rows of the window's slot matrix a fire was
                           # handed (instant inside fire.shard; work:
                           # rows)
    "carry.removed",       # rows the carried matrix swept out in that
                           # advance: keys whose last cell left with its
                           # slice (instant inside fire.shard; work: rows)
    "fire.late",           # a fire of a window whose end is at or under
                           # the newest fired window's: a late re-firing
                           # under allowed lateness (instant inside
                           # fire.dispatch; work: 1)
    "fire.gather",         # the slot matrix one fire program was handed,
                           # padded (instant inside fire.dispatch; work:
                           # padded rows x columns, the cells it gathers)
    "fire.harvest",        # D2H materialization of fire/query results
                           # (work: bytes fetched)
    "slice.retire",        # expired slices' pairs erased from the host
                           # index + their device rows reset (work:
                           # pairs erased; page faults summed)
    "retire.drop",         # pairs that left the host index with their
                           # slice's whole table, no erase per pair
                           # (instant inside slice.retire; work: pairs)
    "sink.write",          # one batch handed to the sink (work: rows)
    "op.process",          # executor: one operator's process_batch
    "op.watermark",        # executor: one operator's process_watermark
    "emit",                # executor: one output left its operator
                           # (instant — durations belong to op.process)
    # the waiting between the work: each hand-over between two threads
    # or between host and device, timed on the side that waits
    "loop.wait_source",    # the task loop blocked at an empty source
                           # queue (Flink's idleTime; a turn that finds a
                           # batch waiting records nothing)
    "source.wait_loop",    # the source pump blocked at a full queue: the
                           # source back-pressured by the loop (Flink's
                           # backPressuredTime; work: 1)
    "source.queue_wait",   # a batch polled and not yet taken by the loop:
                           # watermark assignment, the put and the time
                           # in the queue (externally timed, on the task
                           # loop, one per batch taken; work: entries
                           # still queued behind it; batch: its sequence;
                           # watermark: the one the batch brought)
    "fire.in_flight",      # a dispatched fire out of the host's hands:
                           # dispatch -> the start of its harvest (device
                           # queue, fire program, D2H, the wait to be
                           # polled; externally timed, one per harvest)
    "fire.poll_gap",       # of that, the last look that found the fire
                           # not ready -> the one that found it ready:
                           # an upper bound on how long the result lay
                           # landed (externally timed; 0 where the first
                           # look found it ready, the whole wait where
                           # the harvest blocks)
    "window.emit",         # rows a watermark released have left through
                           # the chain: the origin (poll_batch's return)
                           # of the batch whose watermark dispatched the
                           # fire -> the forward's return (externally
                           # timed, one per fire that produced rows;
                           # watermark: the fire's)
    # control plane
    # a checkpoint on the task loop is three spans in a row; nothing is
    # ingested, dispatched or fired while they run
    "checkpoint.drain",    # every in-flight fire waited for and its rows
                           # forwarded to the sinks before the cut (work:
                           # fires it waited for)
    "checkpoint.snapshot", # every operator's state and the source
                           # positions taken to host arrays (work: bytes
                           # fetched from the device)
    "checkpoint.rows",     # rows of keyed state one table's snapshot
                           # holds: the used rows of a full one, the
                           # dirty and used rows of a delta (instant
                           # inside checkpoint.snapshot; work: rows)
    "checkpoint.tombstones",  # what one table's delta says was freed
                           # since the last snapshot: namespaces and
                           # (key, namespace) pairs (instant inside
                           # checkpoint.snapshot; work: their count;
                           # absent from a full snapshot)
    "checkpoint.write",    # the snapshot serialized, compressed, renamed
                           # into place, older checkpoints retired (work:
                           # bytes on disk)
    "checkpoint.restore",
    "failover.replay",     # partial-failover bounded replay of one range
    "reshard.handoff",     # live key-group migration between mesh sizes
    "serving.lookup",      # one coalesced queryable-state flush
    "serving.replica_publish",  # boundary publish of the read replica
                           # (batch field carries the sealed generation)
    "serving.cache_hit",   # hot-row cache served a lookup batch without
                           # touching the device (instant; batch field
                           # carries the generation the hits were tagged)
    # instants correlated into the same timeline
    "xla.compile",         # real XLA backend compile (jax.monitoring)
    "d2h.transfer",        # device->host materialization (__array__)
    "watchdog.miss",       # a deadline-tracked section ran past budget
    "chaos.inject",        # an armed fault plan fired at a fault point
)

from flink_tpu.observe.recompile_sentinel import (  # noqa: E402,F401
    RecompileSentinel,
    SteadyStateViolation,
    compile_count,
    transfer_count,
)
from flink_tpu.observe.flight_recorder import (  # noqa: E402,F401
    FlightRecorder,
    SpanRecord,
    install_probes,
    recorder,
)
from flink_tpu.observe.lock_sentinel import (  # noqa: E402,F401
    LockOrderViolation,
    LockSentinel,
    NamedLock,
    named_lock,
)
