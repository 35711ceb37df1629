"""Measure every BASELINE.md row on the active backend.

Rows (BASELINE.json):
  1. WordCount, 5 s tumbling window, socket source
  2. Nexmark Q5 — sliding-window (HOP) hot-items COUNT   (bench.py's row)
  3. Nexmark Q7 — tumbling-window MAX + join
  4. Flink SQL GROUP BY HOP over Kafka
  5. Session-window clickstream, 10M distinct keys (spill tier)

Prints one JSON line per row and rewrites BENCHMARKS.md. The parent
never touches JAX: every row runs in a child process, one after another
(a chip belongs to one process at a time), and a failed row fails the
suite. Usage:

    python tools/bench_suite.py                    # the default backend
    JAX_PLATFORMS=cpu python tools/bench_suite.py  # explicit CPU run
    python tools/bench_suite.py --row nexmark_q5   # one row, in-process
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCALE = float(os.environ.get("BENCH_SUITE_SCALE", "1.0"))

_TOOLS = os.path.dirname(os.path.abspath(__file__))


def _child_json(argv, env_defaults=None, env=None, min_lines=1):
    """Run one child to its end; its stdout's JSON-object lines.
    ``env_defaults`` yield to the caller's environment, ``env``
    overrides it. A failed child raises."""
    import subprocess

    child_env = dict(os.environ)
    for k, v in (env_defaults or {}).items():
        child_env.setdefault(k, v)
    child_env.update(env or {})
    proc = subprocess.run([sys.executable] + argv, capture_output=True,
                          text=True, env=child_env, timeout=3600,
                          cwd=os.path.dirname(_TOOLS))
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    if proc.returncode != 0 or len(lines) < min_lines:
        raise RuntimeError(
            f"{argv[0]} rc={proc.returncode}: "
            + (proc.stderr or proc.stdout).strip()[-300:])
    return [json.loads(ln) for ln in lines]


def _tool(name):
    return os.path.join(_TOOLS, name)


def row1_wordcount():
    """Socket-source WordCount (the reference's WindowWordCount)."""
    import socket
    import threading

    from flink_tpu import Configuration, StreamExecutionEnvironment
    from flink_tpu.connectors.sinks import CollectSink
    from flink_tpu.connectors.sources import SocketSource
    from flink_tpu.windowing.assigners import TumblingProcessingTimeWindows

    n_lines = int(200_000 * SCALE)
    line = b"to be or not to be that is the question\n"
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]

    def feed():
        conn, _ = srv.accept()
        chunk = line * 512
        sent = 0
        while sent < n_lines:
            conn.sendall(chunk)
            sent += 512
        conn.close()

    t = threading.Thread(target=feed, daemon=True)
    t.start()
    env = StreamExecutionEnvironment(Configuration({
        "execution.micro-batch.size": 1 << 15}))
    sink = CollectSink()

    def split(batch):
        import numpy as np

        from flink_tpu.core.records import RecordBatch

        words = []
        for ln in batch["line"]:
            words.extend(str(ln).split())
        arr = np.empty(len(words), dtype=object)
        arr[:] = words
        return RecordBatch({"word": arr,
                            "one": np.ones(len(words), dtype=np.int64)})

    (env.add_source(SocketSource("127.0.0.1", port))
        .flat_map(lambda b: [split(b)])
        .key_by("word")
        .window(TumblingProcessingTimeWindows.of(5_000))
        .sum("one").sink_to(sink))
    t0 = time.perf_counter()
    result = env.execute("wordcount")
    dt = time.perf_counter() - t0
    words = n_lines * 10
    return {"metric": "wordcount_socket_words_per_sec",
            "value": round(words / dt, 1), "unit": "words/s",
            "fire_latency_ms": result.metrics.get(
                "window_fire_latency_ms")}


def row2_q5():
    from bench import run

    run(total_records=1 << 21)  # warm
    s = run(total_records=int(20_000_000 * SCALE))
    return {"metric": "nexmark_q5_hop_hot_items_events_per_sec_per_chip",
            "value": round(s["events_per_s"], 1), "unit": "events/s",
            "fire_latency_ms": s["fire_latency_ms"]}


def row3_q7():
    from flink_tpu import Configuration, StreamExecutionEnvironment
    from flink_tpu.benchmarks.nexmark import BidSource, build_q7
    from flink_tpu.connectors.sinks import CollectSink

    def run(total):
        env = StreamExecutionEnvironment(Configuration({
            "execution.micro-batch.size": 1 << 16,
            "state.slot-table.capacity": 1 << 20}))
        sink = CollectSink()
        src = BidSource(total_records=total, num_auctions=10_000,
                        events_per_second_of_eventtime=100_000)
        # 2 s windows (was 10 s): the 10 s shape fired only 10 windows
        # over the row's 100 s of event time, so its percentiles were
        # VACUOUS (n=10, p99 == the single worst sample). 2 s gives
        # n >= 30 fires — the floor below which the suite flags a row's
        # fire percentiles low-confidence.
        build_q7(env, src, size_ms=2_000).sink_to(sink)
        t0 = time.perf_counter()
        result = env.execute("q7")
        return (total / (time.perf_counter() - t0),
                result.metrics.get("window_fire_latency_ms"))

    run(1 << 20)  # warm
    total = int(10_000_000 * SCALE)
    evps, lat = run(total)
    # fire percentiles on EVERY windowed row: the matrix stays
    # comparable (q5 reported them, q7 did not — and the latency-tier
    # gate of ROADMAP item 2 needs this hook on each row)
    return {"metric": "nexmark_q7_max_join_events_per_sec_per_chip",
            "value": round(evps, 1), "unit": "events/s",
            "fire_latency_ms": lat}


def row4_sql_hop_kafka():
    import numpy as np

    from flink_tpu import Configuration, StreamExecutionEnvironment
    from flink_tpu.connectors.kafka import FakeBroker
    from flink_tpu.core.records import RecordBatch
    from flink_tpu.table.environment import StreamTableEnvironment

    total = int(8_000_000 * SCALE)
    parts = 4
    broker = FakeBroker.get("bench")
    broker.create_topic("bench_bids", parts)
    rng = np.random.default_rng(1)
    chunk = 1 << 18
    produced = 0
    while produced < total:
        n = min(chunk, total - produced)
        ks = rng.integers(0, 10_000, n).astype(np.int64)
        vs = rng.random(n)
        ts = (np.arange(produced, produced + n, dtype=np.int64)
              * 1000) // 100_000
        for p in range(parts):
            m = ks % parts == p
            broker.append("bench_bids", p, RecordBatch.from_pydict(
                {"key": ks[m], "value": vs[m], "ts": ts[m]},
                timestamps=ts[m]))
        produced += n

    def run():
        env = StreamExecutionEnvironment(Configuration({
            "execution.micro-batch.size": 1 << 16}))
        tenv = StreamTableEnvironment(env)
        tenv.execute_sql(
            "CREATE TABLE bench_bids (key BIGINT, value DOUBLE, "
            "ts BIGINT, WATERMARK FOR ts AS ts) "
            "WITH ('connector'='kafka', 'topic'='bench_bids', "
            "'broker'='bench')")
        t0 = time.perf_counter()
        rows = tenv.execute_sql("""
            SELECT key, window_end, SUM(value) AS total
            FROM TABLE(HOP(TABLE bench_bids, DESCRIPTOR(ts),
                           INTERVAL '2' SECOND, INTERVAL '10' SECONDS))
            GROUP BY key, window_start, window_end
        """).collect()
        dt = time.perf_counter() - t0
        assert len(rows) > 0
        # the SQL collect path runs env.execute internally; the env
        # keeps the job result so windowed SQL rows report fire
        # percentiles like the DataStream rows
        res = getattr(env, "last_execution_result", None)
        return (total / dt,
                res.metrics.get("window_fire_latency_ms")
                if res is not None else None)

    run()  # warm
    evps, lat = run()
    return {"metric": "sql_group_by_hop_over_kafka_events_per_sec",
            "value": round(evps, 1), "unit": "events/s",
            "fire_latency_ms": lat}


def row5_sessions_10m_keys():
    from flink_tpu import Configuration, StreamExecutionEnvironment
    from flink_tpu.connectors.sinks import CollectSink
    from flink_tpu.connectors.sources import DataGenSource
    from flink_tpu.runtime.watermarks import WatermarkStrategy
    from flink_tpu.windowing.assigners import EventTimeSessionWindows

    total = int(12_000_000 * SCALE)
    keys = 10_000_000

    def run(n):
        env = StreamExecutionEnvironment(Configuration({
            "execution.micro-batch.size": 1 << 16,
            "state.slot-table.capacity": 1 << 19,
            "state.slot-table.max-device-slots": 1 << 19,
        }))
        sink = CollectSink()
        # THRASHING shape (BASELINE row 5): 400k ev/s of event time x
        # 2 s gap ~= 800k concurrently-live sessions vs the 512k device
        # slot budget — the live set EXCEEDS the device, so the run
        # exercises the paged spill tier (slot_table.py
        # spill_layout="pages") under sustained pressure, across ~10M
        # distinct keys. Rounds <= 4 measured a softened 200k ev/s
        # in-budget shape; those numbers are NOT comparable.
        src = DataGenSource(total_records=n, num_keys=keys,
                            events_per_second_of_eventtime=400_000,
                            seed=3)
        (env.from_source(
            src, WatermarkStrategy.for_bounded_out_of_orderness(0))
           .key_by("key")
           .window(EventTimeSessionWindows.with_gap(2_000))
           .sum("value").sink_to(sink))
        t0 = time.perf_counter()
        result = env.execute("sessions")
        dt = time.perf_counter() - t0
        assert len(sink.result()) > 0
        return n / dt, result.metrics.get("window_fire_latency_ms")

    run(1 << 20)  # warm
    evps, lat = run(total)
    return {"metric":
            "session_clickstream_10m_keys_events_per_sec_per_chip",
            "value": round(evps, 1), "unit": "events/s",
            "fire_latency_ms": lat,
            "shape": "400k ev/s event time, 2 s gap, ~800k live "
                     "sessions vs 512k device budget (paged spill), "
                     "10M distinct keys"}


def row5b_mesh_sessions():
    """Row 5 on the MESH session engine (paged spill per shard) — runs
    in a subprocess so the CPU virtual-device flag the mesh needs cannot
    perturb the single-device rows' XLA threading."""
    return _child_json(
        [_tool("bench_mesh_sessions.py")],
        {"BENCH_MESH_SESSION_RECORDS": str(int(4_000_000 * SCALE))})[-1]


def row5c_mesh_sessions_zipf():
    """Row 5's shape with Zipf(1.1) keys and the skew-adaptive plane
    live (load accounting -> key-group moves -> hot-key splitting);
    reports the recovered fraction of the uniform control's
    throughput. Subprocess for the virtual-device flag, like row5b."""
    r = _child_json(
        [_tool("bench_mesh_sessions.py"), "--zipf"],
        {"BENCH_MESH_SESSION_RECORDS": str(int(4_000_000 * SCALE))},
        env={"BENCH_MESH_ZIPF": "1"})[-1]
    sk = r.get("skew") or {}
    r["shape"] = (
        f"{r['shape']}; recovered "
        f"{r['skew_recovery_fraction']:.2f}x of uniform "
        f"({r['uniform_events_per_s']:,.0f} ev/s), "
        f"{sk.get('rebalances', 0)} rebalances / "
        f"{sk.get('groups_moved', 0)} groups moved / "
        f"{sk.get('keys_split', 0)} keys split "
        f"({sk.get('salted_records', 0):,} records salted), "
        f"imbalance {sk.get('imbalance_contiguous', 0)} -> "
        f"{sk.get('imbalance_live', 0)}")
    return r


def row6_queryable_lookups():
    """High-QPS queryable-state serving: 2 concurrent jobs on one mesh,
    client threads issuing 256-key batched point lookups (the tenancy
    serving plane). Subprocess for the virtual-device flag, like the
    mesh row."""
    return _child_json([_tool("serving_smoke.py")], {
        "SERVING_SMOKE_RECORDS": str(int(400_000 * SCALE)),
        "SERVING_SMOKE_CLIENTS": "16",
        "SERVING_SMOKE_LOOKUP_BATCH": "256",
        "SERVING_SMOKE_KEYS": "4096",
        # the r19 native-fast-path operating point: 2 ms client pause
        # (the packed path holds the staleness SLO there; the dict
        # control does NOT — its recorded number stays at its own best
        # point, 5 ms)
        "SERVING_SMOKE_CLIENT_PAUSE_MS": "2"})[-1]


def row7_shard_loss_recovery():
    """Partial failover: kill 1 of 4 shards mid-stream (the chaos
    smoke's shard-loss scenario at bench scale — 1M events, forced
    paged eviction) and report wall-clock recovery: survivor
    evacuation + mesh rebuild + checkpoint-unit restore of ONLY the
    dead range + bounded replay of ONLY its records."""
    r = _child_json(
        ["-c",
         "import sys; sys.argv=['chaos_smoke']; "
         "import tools.chaos_smoke as cs; "
         "sys.exit(cs.shard_loss_scenario())"],
        {"CHAOS_SHARD_LOSS_KEYS": str(int(1_000_000 * SCALE)),
         "CHAOS_SHARD_LOSS_PER_STEP": str(int(125_000 * SCALE)),
         "CHAOS_SHARD_LOSS_SLOTS": str(1 << 14)})[-1]
    return {
        "metric": "shard_loss_recovery_ms",
        "value": r["shard_loss_recovery_ms"],
        "shape": (f"{r['events']:,} events over {r['shards']} shards, "
                  f"1 shard killed mid-stream (device.lost): "
                  f"{r['shard_restores']} range restored from its "
                  f"checkpoint unit, {r['records_replayed']:,} records "
                  f"replayed (bound: events/shards = "
                  f"{r['events'] // r['shards']:,}), output "
                  "oracle-identical"),
    }


def row8_mesh_sessions_2proc():
    """Pod-scale row: the mesh_sessions shape split across 2 REAL
    processes (jax.distributed + gloo CPU collectives), each owning
    half the key-group space with its own metadata plane, spill tier
    and checkpoint units, exchanging records over the DCN axis of the
    process-spanning mesh ON DEVICE (tools/multiproc_smoke.py). The
    row records the aggregate throughput and the scaling factor vs the
    same-box 1-process run — near-linear on real multi-core/multi-host
    boxes; a 1-core CI box time-shares the clock and reports the
    pod-protocol overhead instead (NOTES_r18.md)."""
    r = _child_json(
        [_tool("multiproc_smoke.py")],
        {"MP_SMOKE_RECORDS": str(int(262_144 * SCALE))})[-1]
    r["unit"] = "events/s aggregate"
    r["shape"] += (
        f"; 1-proc same-box {r['single_proc_events_per_s']:,.0f} ev/s "
        f"-> scaling {r['scaling_x']}x, "
        f"{r['cross_host_rows']:,} rows crossed the DCN axis")
    return r


def row9_serving_mp():
    """Serving-tier row: N frontend PROCESSES attach the owner's shm
    hot-cache arena (tools/bench_serving_mp.py) and run the probe →
    packed-reply loop entirely in their own address space — no GIL
    shared with the owner, no pipe on the hit path — while the owner
    keeps priming fresh generations at the publish cadence. The row
    records the aggregate shm lookups/s off the SHARED arena header
    counters (fe_stats, not wall division) and the scaling factor vs
    the owner's own 1-process packed loop; near-linear on multi-core
    boxes, time-shared on a 1-core CI box (NOTES_r21.md)."""
    return _child_json(
        [_tool("bench_serving_mp.py")],
        {"BENCH_SERVING_MP_BATCHES": str(int(2000 * SCALE))})[-1]


def _join_rows():
    """Both join rows from tools/bench_joins.py in ONE subprocess (the
    mesh needs the virtual-device flag, like row5b; the tool prints one
    JSON line per row)."""
    return _child_json(
        [_tool("bench_joins.py")],
        {"BENCH_JOIN_RECORDS": str(int(4_000_000 * SCALE)),
         "BENCH_JOIN_REQUIRE_SPILL": "1"}, min_lines=2)[-2:]


def row_cep():
    """Device-vectorized CEP at the row-5 thrashing shape: a 2-stage
    within-window sequence over 10M keys, live partials >> device
    budget (forced paged eviction), raced against the host CepOperator
    oracle at the same shape — the bench FAILS itself if the device
    engine loses or the spill tier never engages. Subprocess for the
    virtual-device flag, like row5b."""
    return _child_json(
        [_tool("bench_cep.py")],
        {"BENCH_CEP_RECORDS": str(int(4_000_000 * SCALE)),
         "BENCH_CEP_REQUIRE_SPILL": "1",
         "BENCH_CEP_REQUIRE_WIN": "1"})[-1]


_JOIN_CACHE = {}


def _join_row(idx):
    def run():
        if "rows" not in _JOIN_CACHE:
            _JOIN_CACHE["rows"] = _join_rows()
        return _JOIN_CACHE["rows"][idx]

    return run


ROWS = [("wordcount_socket", row1_wordcount),
        ("nexmark_q5", row2_q5),
        ("nexmark_q7", row3_q7),
        ("sql_hop_kafka", row4_sql_hop_kafka),
        ("sessions_10m_keys", row5_sessions_10m_keys),
        ("mesh_sessions_10m_keys", row5b_mesh_sessions),
        ("mesh_sessions_zipf", row5c_mesh_sessions_zipf),
        ("queryable_lookups", row6_queryable_lookups),
        ("shard_loss_recovery", row7_shard_loss_recovery),
        ("nexmark_q8_windowed_join", _join_row(0)),
        ("interval_join_10m_keys", _join_row(1)),
        ("cep_patterns_10m_keys", row_cep),
        ("mesh_sessions_2proc", row8_mesh_sessions_2proc),
        ("serving_mp_lookups", row9_serving_mp)]


#: rows whose job runs inside the process that calls them; the suite
#: runs each as a ``--row`` child. Every other row starts its own child.
IN_PROCESS_ROWS = ("wordcount_socket", "nexmark_q5", "nexmark_q7",
                   "sql_hop_kafka", "sessions_10m_keys")


def run_row(name):
    """Child mode (``--row NAME``): one in-process row, its line naming
    the device it ran on."""
    import warnings

    warnings.filterwarnings("ignore")
    from flink_tpu.platform import enable_compilation_cache

    enable_compilation_cache()
    r = dict(ROWS)[name]()
    import jax

    dev = jax.devices()[0]
    r["backend"] = dev.platform
    r["device_kind"] = dev.device_kind
    print(json.dumps(r), flush=True)


def main():
    if len(sys.argv) == 3 and sys.argv[1] == "--row":
        return run_row(sys.argv[2])
    results = []
    for name, fn in ROWS:
        # no try/except: a failed row fails the suite
        if name in IN_PROCESS_ROWS:
            r = _child_json([os.path.abspath(__file__), "--row", name])[-1]
        else:
            r = fn()
        # the child-tool rows do not name their device: label them with
        # what the in-process rows of this same run reported
        if "backend" not in r:
            r["backend"] = results[0]["backend"]
        lat = r.get("fire_latency_ms")
        if lat and lat.get("count", 0) < 30:
            # a windowed row that fired < 30 times has vacuous
            # percentiles (p99 == the worst 1-2 samples): flag it so
            # nobody gates or compares against noise
            r["fire_latency_low_confidence"] = True
        results.append(r)
        print(json.dumps(r), flush=True)
    platform = results[0]["backend"]
    lines = [
        "# BENCHMARKS — all BASELINE.md rows",
        "",
        f"Backend: **{platform}** · scale {SCALE} · "
        f"{time.strftime('%Y-%m-%d %H:%M')}",
        "",
        "| Row | Metric | Value | Unit |",
        "|---|---|---|---|",
    ]
    for (name, _), r in zip(ROWS, results):
        val = f"{r['value']:,.0f}"
        extra = ""
        if r.get("shape"):
            extra = f" — {r['shape']}"
        if r.get("spill"):
            sp = r["spill"]
            extra += (f" — spill: {sp['pages_evicted']} pages evicted, "
                      f"{sp['pages_reloaded']} reloaded, "
                      f"{sp['rows_split_on_reload']} rows split on "
                      f"reload, {sp.get('rows_compacted', 0)} compacted")
        if r.get("breakdown"):
            bd = r["breakdown"]
            if "host_prep_s" in bd:
                extra += (f" — host-prep {bd['host_prep_s']}s / "
                          f"device-step {bd['device_step_s']}s / harvest "
                          f"{bd['harvest_s']}s of {bd['total_s']}s")
                if "host_prep_fraction" in bd:
                    extra += (f" (host-prep fraction "
                              f"{bd['host_prep_fraction']})")
                if bd.get("native_sweep_s"):
                    extra += (f", native sweeps {bd['native_sweep_s']}s")
            elif "ingest_s" in bd:  # the join benches' phase split
                extra += (f" — ingest {bd['ingest_s']}s / probe+fire "
                          f"{bd['probe_fire_s']}s / harvest "
                          f"{bd['harvest_s']}s of {bd['total_s']}s")
        if r.get("shuffle_mode"):
            extra += f", {r['shuffle_mode']}-mode shuffle"
        if r.get("matches"):
            extra += f" — {r['matches']:,} matches"
        if r.get("fire_latency_ms"):
            lat = r["fire_latency_ms"]
            conf = (" LOW-CONFIDENCE (n<30)"
                    if r.get("fire_latency_low_confidence") else "")
            extra += (f" (fire p50 {lat['p50']:.0f} ms / "
                      f"p99 {lat['p99']:.0f} ms, n={lat['count']}{conf})")
        lines.append(f"| {name} | {r['metric']} | {val}{extra} | "
                     f"{r.get('unit', '')} |")
    lines.append("")
    lines.append("Generated by `tools/bench_suite.py`; the proxy "
                 "baseline discussion lives in `BASELINE.md`.")
    lines.append("")
    lines.append(
        "Methodology: headline values are the MEDIAN of post-warm reps "
        "(`bench.py` and `tools/bench_mesh_sessions.py`; best/all reps "
        "travel as secondary JSON fields). The mesh-sessions row drives "
        "the mesh engine's pipelined path (dispatch-ahead + async "
        "coalesced fire harvests) on 8 virtual CPU devices sharing one "
        "host's cores — a kernel-overhead lower bound; on TPU hardware "
        "the shards are real chips and the budget is per-chip HBM. Its "
        "spill counters come from the lazy-tombstone paged tier "
        "(NOTES_r6.md): `rows_split_on_reload` stays ~0 by design, and "
        "`tools/tier1.sh` gates on the page-rewrite amplification "
        "`(rows_split_on_reload + rows_compacted) / rows_reloaded`.")
    lines.append("")
    lines.append(
        "Fused-path methodology (r11): the mesh-sessions row runs "
        "`shuffle.mode=device` — flat columns go up in ONE `device_put` "
        "and a single compiled program segment-sorts, "
        "`all_to_all`-exchanges and scatter-aggregates them "
        "(`parallel/shuffle.py`; design in NOTES_r11.md). The breakdown "
        "attributes device work surfacing inside `process_batch` "
        "(dispatch-fence blocks + the engine-timed inline device "
        "interactions) to `device_step_s`, so `host_prep_fraction` "
        "measures genuine host work (sessionization, slot resolution, "
        "flat staging); `tools/tier1.sh` gates it via "
        "`BENCH_HOST_PREP_BUDGET` in device mode.")
    lines.append("")
    lines.append(
        "Native metadata plane (r12): the mesh-sessions row runs the "
        "session metadata (sessionize -> absorb -> slot-resolve -> pop) "
        "as ONE C sweep per batch (`native/sessions.cpp` via "
        "`windowing/session_native.py`; design in NOTES_r12.md), with "
        "the session's device slot FOLDED into its metadata row so "
        "singleton sessions skip the state-plane hash probe "
        "(fold-verify: a stale fold falls back to the probe, never a "
        "wrong row). `native_sweep_s` reports the C share of the "
        "breakdown; `native_session_plane` in the row JSON says which "
        "plane ran, and the tier-1 smoke FAILS if the native plane was "
        "requested but unavailable. The pure-Python plane "
        "(`FLINK_TPU_NATIVE_SESSIONS=0`) is bit-identical in fires, "
        "snapshots and spill counters (test-pinned).")
    lines.append("")
    lines.append(
        "The queryable-lookups row is `tools/serving_smoke.py` at bench "
        "scale: two concurrent ingesting jobs share one mesh and the "
        "compiled-program cache while client threads issue batched "
        "point lookups through the READ-REPLICA serving plane (r17) "
        "and, since r19, the NATIVE FAST PATH: the whole key batch "
        "probes a GIL-free seqlock-stamped table of PACKED composed "
        "results (`native/hotcache.cpp`) in ONE C call, hit results "
        "stay packed until a consumer reads them "
        "(`lookup_batch_packed`), the publish harvest primes via one "
        "packed buffer, and session entries re-prime under their "
        "MOVING end instead of invalidating. Methodology: the headline "
        "runs at the fast path's operating point (2 ms client pause); "
        "the same-box control (`FLINK_TPU_NATIVE_HOTCACHE=0` + "
        "`SERVING_SMOKE_PACKED=0`, the r17 path) is recorded at ITS "
        "best operating point that still holds the replica staleness "
        "SLO (5 ms pause — at 2 ms the GIL-held dict path starves the "
        "publish loop to seconds of staleness and is rejected), so "
        "both numbers describe a plane that actually serves fresh "
        "boundaries. The tier-1 smoke runs the same script smaller and "
        "FAILS on any steady-state compile, p99 over 25 ms, throughput "
        "under 350k lookups/s, a native hit path < 2x cheaper than the "
        "Python dict path (per-hit microbench), staleness p99 over "
        "1 s, a packed-vs-dict mismatch, a silent Python-cache "
        "fallback when the native library built, vacuous cache/publish "
        "activity, or a quota violation (design notes in NOTES_r10.md, "
        "NOTES_r17.md and NOTES_r19.md).")
    lines.append("")
    lines.append(
        "Pod scale (r18): the mesh_sessions_2proc row is "
        "`tools/multiproc_smoke.py` at bench scale — 2 REAL processes "
        "(`jax.distributed.initialize` + gloo CPU collectives), each "
        "owning half the key-group space (`host_key_group_ranges`) "
        "with its own session-metadata plane, spill tier and per-range "
        "checkpoint units; records reach their owner over the DCN axis "
        "of the process-spanning mesh ON DEVICE "
        "(`parallel/pod.PodDataPlane`), and each process's fused "
        "exchange is the intra-host ICI stage. The row reports "
        "aggregate ev/s and the scaling factor vs the same-box "
        "1-process run. CAVEAT: on a 1-core CI box both processes "
        "time-share one clock, so the scaling factor there measures "
        "pod-protocol overhead (exchange + harvest + re-stage), not "
        "the near-linear speedup a multi-core/multi-host box shows; "
        "the smoke's correctness gates (bit-identity, 0 steady-state "
        "compiles, cross-host traffic, kill-1-of-2 recovery) hold "
        "regardless (NOTES_r18.md).")
    lines.append("")
    lines.append(
        "Skew-adaptive plane (r20): the mesh_sessions_zipf row is "
        "`tools/bench_mesh_sessions.py --zipf` — the same 10M-key "
        "shape with the key column drawn Zipf(1.1), so a handful of "
        "keys carry most of the stream and the contiguous key-group "
        "layout pins one shard. The driver wires the skew ladder "
        "(detect -> rebalance -> split): `parallel/load.py` accounts "
        "per-key-group load from routed batches (EWMA + a Misra-Gries "
        "hot-key sketch), `autoscale/rebalance.py` plans greedy "
        "hottest-group-to-coldest-shard MOVES (hysteresis + cooldown) "
        "applied live via `reassign_key_groups` (P unchanged, same "
        "handoff discipline as reshard, own chaos fault point), and "
        "keys that dominate their group — which no group move can fix "
        "— are SPLIT via `register_hot_key`: records salt into "
        "sub-rows pre-aggregated on their own shards and fold back at "
        "fire in a fixed order (bit-identical for min/max/integer "
        "sums; float sums opt in via allow_inexact). The row reports "
        "zipf throughput, the uniform control, their ratio "
        "(`skew_recovery_fraction`) and the responder counters; "
        "`tools/tier1.sh` runs the same plane smaller via "
        "`tools/skew_smoke.py` and FAILS if recovery drops below "
        "`BENCH_SKEW_RECOVERY`, if no live move happened, if nothing "
        "was salted, or if the rebalanced/salted output diverges from "
        "the single-device oracle (NOTES_r20.md).")
    lines.append("")
    lines.append(
        "Multi-process serving tier (r21): the serving_mp_lookups row "
        "is `tools/bench_serving_mp.py` — N frontend PROCESSES "
        "(`tenancy/frontend.py FrontendPool`) attach the owner's "
        "hot-cache arena over shared memory (`hc_attach` on the "
        "contiguous mmap-able arena, `native/hotcache.cpp`) and run "
        "the probe -> packed-reply loop entirely in their own address "
        "space: the hit path shares NO GIL and crosses NO pipe — the "
        "seqlock stamp protocol is address-free, so a frontend reads "
        "the same generation-consistent rows the owner publishes, "
        "torn reads retry and then miss (never serve a mix). Cold "
        "misses cross a bounded pipe to the owner and are answered "
        "from the replica plane, so the staleness SLO is unchanged. "
        "The bench primes the arena, measures the owner's own "
        "1-process packed loop for scaling context, then drives the "
        "same batch shape from every frontend while the owner keeps "
        "priming fresh generations at the publish cadence; the "
        "aggregate comes from the SHARED arena-header per-frontend "
        "counters (`fe_stats`), not wall-clock division, and the row "
        "FAILS on a sub-0.98 hit rate or a frozen (unprimed) table. "
        "On a 1-core CI box the frontends time-share the clock; "
        "`tools/tier1.sh` runs `tools/frontend_smoke.py` which gates "
        "the structural claims regardless of core count: zero torn "
        "reads across a cross-process seqlock fuzz, bit-identical "
        "parity with the owner's dict oracle, staleness-SLO held "
        "through the frontend path, and a real frontend-kill failover "
        "(design in NOTES_r21.md).")
    lines.append("")
    lines.append(
        "Streaming-join rows (r14): `tools/bench_joins.py` drives the "
        "device-native interval-join engine (`flink_tpu/joins/` — dual "
        "keyed slot tables co-partitioned by the keyBy exchange, one "
        "banded segment-intersection program per batch, design in "
        "NOTES_r14.md). `fire_latency_ms` is the EMIT latency: wall "
        "time from an arriving batch to its matches materialized on "
        "the host (the two-input analogue of window fire latency — "
        "every windowed row reports fire percentiles since r14, which "
        "is also the hook ROADMAP item 2's latency gate needs). The "
        "10M-key row forces paged eviction (live rows >> device "
        "budget) and FAILS as vacuous if spill never engages; "
        "`tools/join_smoke.py` gates the same engine bit-identical to "
        "its host-numpy oracle in tier-1.")
    lines.append("")
    lines.append(
        "CEP row (r22): `tools/bench_cep.py` drives the "
        "device-vectorized mesh NFA engine "
        "(`flink_tpu/cep/mesh_engine.py` — per-key computation states "
        "as int32 bitmask columns on the state plane, ONE compiled "
        "gather/scan/scatter advance program per fire, design in "
        "NOTES_r22.md) at the row-5 thrashing shape: a 2-stage "
        "within-window sequence over 10M keys whose live partial set "
        "sits far above the device budget, so the paged tier churns "
        "(asserted — `BENCH_CEP_REQUIRE_SPILL` fails a vacuous run). "
        "The SAME shape runs on the host `CepOperator` NFA — the "
        "bit-identity oracle every CEP gate diffs against — and the "
        "row reports `speedup_vs_host`; `BENCH_CEP_REQUIRE_WIN` makes "
        "a device loss a bench failure, not a footnote. "
        "`fire_latency_ms` is the emit latency from a watermark "
        "advance to matches on the host; `tools/cep_smoke.py` gates "
        "the engine bit-identical (values AND emission order) to the "
        "oracle in tier-1, including a replica-plane matched-pattern "
        "lookup leg.")
    lines.append("")
    lines.append(
        "The shard-loss-recovery row runs `tools/chaos_smoke.py`'s "
        "shard-loss scenario at bench scale: an injected `device.lost` "
        "kills 1 of 4 shards at a batch boundary mid-stream, and the "
        "measured span covers the whole partial failover — survivor "
        "evacuation (live-reshard row lift, dirtiness intact), mesh "
        "rebuild over the remaining devices, restore of ONLY the dead "
        "shard's key groups from their shard-granular checkpoint unit "
        "(flink_tpu/checkpoint/sharded.py), and bounded replay of ONLY "
        "that range's records from the unit's source position. The "
        "tier-1 smoke runs the same scenario smaller and FAILS if the "
        "replay volume exceeds events/shards or the committed output "
        "diverges from the fault-free oracle (NOTES_r13.md).")
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCHMARKS.md")
    with open(out, "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"# wrote {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
