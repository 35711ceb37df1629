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
    "join.index",          # the window join's per-batch host work: the
                           # batch's slices and shards, its slots from its
                           # slices' pages (inside batch.ingest; work:
                           # rows inserted)
    "join.staged",         # rows of a batch indexed while the join's
                           # match ran and held on the host, not put
                           # (instant inside join.index; work: rows)
    "join.match",          # the window join's fire on the host: the
                           # window's pages handed to the match program,
                           # the output sized (inside fire.dispatch; work:
                           # output rows sized, at least the pairs)
    "join.rows",           # rows of both sides in a fired window (instant
                           # inside join.match; work: rows)
    "join.pairs",          # pairs a window join's fire emitted, or the
                           # pairs a regular join's probe found that its
                           # residual kept (instant inside fire.harvest,
                           # where the count first reaches the host;
                           # work: pairs)
    "join.probe",          # the regular join's probe of a batch against
                           # the other side's held rows: the batch's
                           # keys looked up on the host, the probe
                           # program dispatched (inside batch.ingest;
                           # work: candidate pairs, before the residual)
    "join.insert",         # the regular join's insert of a batch into
                           # its side: the batch's keys, their chains and
                           # the put of its rows (inside batch.ingest;
                           # work: rows)
    "join.held_rows",      # rows a regular join's batch added to the
                           # rows it holds for the job's life (instant
                           # inside join.insert; work: rows; shard: the
                           # side)
    "join.held_bytes",     # device bytes those rows hold (instant inside
                           # join.insert; work: bytes; shard: the side)
    "join.retire",         # fired slices' pages back to the free lists,
                           # once the last fire reading them is harvested
                           # (work: rows dropped)
    "agg.fold",            # an unwindowed GROUP BY's batch folded into
                           # its table and the touched keys' new values
                           # read back (work: rows)
    "agg.groups",          # the distinct keys a GROUP BY's batch took
                           # to its table, grouped from its rows in one
                           # hash pass (instant inside agg.fold; work:
                           # keys)
    "agg.retract",         # retraction rows (UPDATE_BEFORE / DELETE) a
                           # GROUP BY folded with their negative sign
                           # (instant inside agg.fold; work: rows)
    "agg.emit",            # a GROUP BY's changelog built on the host from
                           # the touched keys' new and last emitted values
                           # (work: rows)
    "agg.changelog",       # rows of that changelog (instant after
                           # agg.emit; work: rows)
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
    # a checkpoint stops the task loop for three spans: the drain and the
    # snapshot at the cut, the write when its persist has finished;
    # nothing is ingested, dispatched or fired while they run. Between
    # them the loop runs on and the writer thread persists the cut
    "checkpoint.drain",    # every in-flight fire waited for and its rows
                           # forwarded to the sinks before the cut (work:
                           # fires it waited for)
    "checkpoint.snapshot", # every operator's state and the source
                           # positions taken to host arrays and made the
                           # writer's own: arrays split off, metadata
                           # pickled (work: bytes fetched from the device)
    "checkpoint.offloop",  # a cut handed to the writer thread (instant
                           # inside checkpoint.snapshot; work: 1)
    "checkpoint.rows",     # rows of keyed state one table's snapshot
                           # holds: the used rows of a full one, the
                           # dirty and used rows of a delta (instant
                           # inside checkpoint.snapshot; work: rows)
    "checkpoint.tombstones",  # what one table's delta says was freed
                           # since the last snapshot: namespaces and
                           # (key, namespace) pairs (instant inside
                           # checkpoint.snapshot; work: their count;
                           # absent from a full snapshot)
    "checkpoint.persist",  # the writer thread: the cut's arrays
                           # compressed into .npz files, CRCs, manifest,
                           # the atomic rename, older checkpoints retired
                           # (work: bytes on disk)
    "checkpoint.persist_wait",  # the task loop found the persist in
                           # flight unfinished where it must be complete:
                           # a cut due, a savepoint, the end of input, a
                           # failure (instant inside checkpoint.write;
                           # work: 1)
    "checkpoint.write",    # the task loop completes a persisted
                           # checkpoint: waits for it where it must,
                           # makes it the next delta's base, notifies the
                           # operators (two-phase sinks commit) (work:
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
