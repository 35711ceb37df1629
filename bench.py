"""Headline benchmark: Nexmark Q5 — hot items over a sliding window.

keyBy(auction) -> HOP(10 s size, 2 s slide) -> COUNT -> per-window arg-max,
on the synthetic Nexmark bid stream (flink_tpu/benchmarks/nexmark.py). Runs
the full framework path: DataStream API -> local executor -> native slot-map
index -> jitted scatter/gather kernels on the active JAX backend.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "device_count", ...}.
Diagnostics (fire latency percentiles, result counts) go to stderr.

It runs on the devices JAX gives it, in this one process, and exits
non-zero on any failure: no probe child, no fallback to another
platform, no reduced re-run. It measures the CPU only when
``JAX_PLATFORMS=cpu`` is set explicitly (as ``tools/tier1.sh`` does), and
its line names ``platform``, ``device_kind`` and ``device_count``.

Baseline note (see BASELINE.md): the reference (Apache Flink, JVM) cannot be
built or executed in this zero-egress container and publishes no absolute
numbers in-repo. vs_baseline is computed against the documented proxy of
500_000 events/s/chip for Flink's RocksDB-backed windowed aggregation; the
>=10x target of BASELINE.json corresponds to vs_baseline >= 10.
"""

import json
import os
import sys
import time

PROXY_BASELINE_EVENTS_PER_S = 500_000.0


def run(total_records: int, num_auctions: int = 100_000,
        batch_size: int = None, layout: str = "slots") -> dict:
    from flink_tpu import Configuration, StreamExecutionEnvironment
    from flink_tpu.benchmarks.nexmark import BidSource, build_q5
    from flink_tpu.connectors.sinks import CollectSink

    import jax

    on_tpu = jax.default_backend() not in ("cpu",)
    if batch_size is None:
        # Platform-conditional defaults, swept 2026-07-30/31 when the
        # chip sat behind a ~64 ms round trip (1M-row batches amortized
        # it: ~5.8M ev/s against ~0.9M at 131k rows). Not re-swept with
        # the process beside the chip (ROADMAP queue 1 item 2).
        # CPU: 64k-row batches + dispatch-ahead 1 measured BOTH the
        # best throughput (3.28M ev/s) and fire p50/p99 = 41/91 ms
        # over 204 samples — deep pipelining only queues fires behind
        # scatter work when the "device" is the same core.
        batch_size = int(os.environ.get(
            "BENCH_BATCH_SIZE", 1 << 20 if on_tpu else 1 << 16))
    env = StreamExecutionEnvironment(Configuration({
        "execution.micro-batch.size": batch_size,
        # headroom above the live (key x slice) footprint so ring/column
        # growth never interrupts the measured run
        "state.slot-table.capacity": 1 << 22,
        "state.window-layout": layout,
        # dispatch pipelining depth: deeper hides per-batch dispatch
        # latency, shallower keeps fire kernels from queueing behind
        # scatters
        "execution.pipeline.max-dispatch-batches": int(
            os.environ.get("BENCH_DISPATCH_AHEAD", 8 if on_tpu else 1)),
    }))
    sink = CollectSink()
    # 100k events/s of event time -> a 2 s slide covers ~200k events, a 10 s
    # window ~1M; the default 40M records span 400 s of event time = 200 HOP
    # slide boundaries, so the fire-latency p99 is over >=200 fire samples
    # (one per watermark advance that closes windows) rather than the ~24
    # the old geometry produced.
    src = BidSource(total_records=total_records, num_auctions=num_auctions,
                    events_per_second_of_eventtime=100_000)
    build_q5(env, src, size_ms=10_000, slide_ms=2_000,
             device_top_k=16).sink_to(sink)
    t0 = time.perf_counter()
    result = env.execute("nexmark-q5-hot-items")
    elapsed = time.perf_counter() - t0
    return {
        "events_per_s": total_records / elapsed,
        "elapsed_s": elapsed,
        "results": len(sink.result()),
        "fire_latency_ms": result.metrics.get("window_fire_latency_ms"),
    }


def emit(value: float, extra: dict = None) -> None:
    import jax

    dev = jax.devices()[0]
    line = {
        "metric": "nexmark_q5_hop_hot_items_events_per_sec_per_chip",
        "value": round(value, 1),
        "unit": "events/s",
        "vs_baseline": round(value / PROXY_BASELINE_EVENTS_PER_S, 3),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }
    if extra:
        line.update(extra)
    print(json.dumps(line))
    sys.stdout.flush()


def main():
    import warnings

    warnings.filterwarnings("ignore")
    from flink_tpu.platform import enable_compilation_cache

    enable_compilation_cache()
    import jax

    on_cpu = jax.default_backend() == "cpu"
    if on_cpu and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit("bench.py: JAX found no accelerator; set "
                 "JAX_PLATFORMS=cpu to measure the CPU on purpose")

    total = int(os.environ.get("BENCH_RECORDS", 40_000_000))
    # Measure BOTH window-state layouts and report the better one: the
    # pane layout removes the per-fire host->device slot matrix, the
    # slot layout is the measured incumbent. On CPU the pane layout is
    # not competitive (measured 2026-07-31: 185k ev/s vs slots' 3.28M —
    # its dense per-fire reductions only pay off when they delete
    # host->device transfers); don't spend minutes measuring it there.
    stats = None
    best_layout = None
    for layout in (("slots",) if on_cpu else ("panes", "slots")):
        # Warmup must cover the FIRE path too: at 100k events/s of
        # event time the first HOP window closes at 2 s, so the warmup
        # needs >200k records for the watermark to cross a window end
        # and compile the fire/merge kernels (at the production
        # num_auctions so the pad buckets match the measured run).
        run(total_records=1 << 21, num_auctions=100_000, layout=layout)
        # Steady-state: repeat the measured pass and take the MEDIAN
        # rep as the headline (best-of overstates sustained
        # throughput). Best and all reps stay in the JSON as secondary
        # fields.
        reps = []
        for rep in range(max(int(os.environ.get("BENCH_REPS", 3)), 1)):
            r = run(total_records=total, layout=layout)
            print(f"# layout={layout} rep {rep}: "
                  f"{r['events_per_s']:.0f} events/s, "
                  f"fire_latency={r['fire_latency_ms']}",
                  file=sys.stderr)
            reps.append(r)
        by_rate = sorted(reps, key=lambda r: r["events_per_s"])
        s = by_rate[len(by_rate) // 2]  # median (upper-mid for even)
        s["rep_events_per_s"] = [round(r["events_per_s"], 1)
                                 for r in reps]
        s["best_events_per_s"] = round(by_rate[-1]["events_per_s"], 1)
        if stats is None or s["events_per_s"] > stats["events_per_s"]:
            stats, best_layout = s, layout
    print(f"# q5 best layout={best_layout}: {stats['results']} winner "
          f"rows, fire_latency={stats['fire_latency_ms']}", file=sys.stderr)
    emit(stats["events_per_s"],
         extra={"layout": best_layout,
                "rep_events_per_s": stats["rep_events_per_s"],
                "best_events_per_s": stats["best_events_per_s"]})


if __name__ == "__main__":
    main()
