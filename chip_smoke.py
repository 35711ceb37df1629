"""chip_smoke.py — the quickest proof that the system starts on the chip.

    python chip_smoke.py              # one TPU chip: both one-chip jobs
    python chip_smoke.py --chips 4    # four chips: the mesh job only

One process. It requires ``jax.devices()[0].platform == "tpu"`` (there is
no CPU mode and no size knob through the environment), then submits real
jobs through ``StreamExecutionEnvironment`` -> ``key_by().window()
.aggregate()`` -> ``sink_to()`` -> ``env.execute()`` with every mode left
at its default, and checks each job's output against a plain NumPy
reference written here.

Standard output carries one JSON object per phase and, as its LAST line,
exactly ``{"ok": ..., "device": {"platform", "kind", "count"}}``. That
line is written by :func:`verdict` alone, on every exit path, followed
only by ``os._exit``. ``main`` keeps a private duplicate of descriptor 1
for these lines and points descriptor 1 at standard error, so nothing
else — print sinks, C++ libraries, warnings, atexit hooks, threads — can
write into or after it.
"""

import argparse
import collections
import json
import os
import sys
import threading
import time

NO_DEVICE = {"platform": "none", "kind": "none", "count": 0}

#: one ``on_watermark`` call of the tapped window operator
FireCall = collections.namedtuple(
    "FireCall", "watermark compiles_at_entry compiles_at_exit fired")

_verdict_once = threading.Lock()


def verdict(out, ok, device):
    """Build and write the last line, then leave at once. The ONLY
    writer of that line; never returns, and runs at most once."""
    if not _verdict_once.acquire(blocking=False):
        threading.Event().wait()  # another path is already leaving
    line = json.dumps({
        "ok": bool(ok),
        "device": {"platform": str(device["platform"]),
                   "kind": str(device["kind"]),
                   "count": int(device["count"])}})
    sys.stdout.flush()
    sys.stderr.flush()
    out.write(line + "\n")
    out.flush()
    os._exit(0 if ok else 1)


def report(out, **fields):
    """One JSON object on its own (earlier) stdout line."""
    out.write(json.dumps(fields, default=str) + "\n")
    out.flush()


def require(cond, why):
    """A check that holds under ``python -O`` too."""
    if not cond:
        raise AssertionError(str(why))


def jax_device():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------- probes


class _Compiles:
    """Compile and persistent-cache traffic, from jax.monitoring through
    the repo's recompile sentinel."""

    def __init__(self):
        import jax

        from flink_tpu.observe import recompile_sentinel as rs

        rs.install()
        self.rs = rs
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        rs.add_compile_listener(self._on_compile)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_compile(self, secs):
        self.seconds += secs

    def _on_event(self, name, **kwargs):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return {"compiles": self.rs.compile_count(),
                "compile_seconds": self.seconds,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def _delta(after, before):
    return {k: after[k] - before[k] for k in after}


def _cache_entries():
    from flink_tpu.platform import compilation_cache_dir

    d = compilation_cache_dir()
    return d, (len(os.listdir(d)) if d and os.path.isdir(d) else 0)


def _peak_bytes(devices):
    stats = [d.memory_stats() or {} for d in devices]
    return [s.get("peak_bytes_in_use") for s in stats]


def _tap_window_operator(t):
    """Observe (never alter) the window operator the executor builds for
    transformation ``t``: keeps the instance and, for every
    ``on_watermark`` call, the watermark, the process's XLA compile
    count at entry and exit, the windows it fired, and the devices each
    dispatched fire output sat on before its harvest."""
    from flink_tpu.observe.recompile_sentinel import compile_count

    seen = {"ops": [], "fire_devices": set(), "calls": []}
    make = t.operator_factory

    def factory():
        op = make()
        seen["ops"].append(op)
        opened = op.open

        def open_and_tap(ctx):
            opened(ctx)
            fire = op.windower.on_watermark

            def tapped(watermark, *args, **kwargs):
                at_entry = compile_count()
                fired = fire(watermark, *args, **kwargs)
                for f in fired:
                    for a in getattr(f, "arrays", ()):
                        seen["fire_devices"] |= set(a.devices())
                seen["calls"].append(FireCall(
                    int(watermark), at_entry, compile_count(), len(fired)))
                return fired

            op.windower.on_watermark = tapped

        op.open = open_and_tap
        return op

    t.operator_factory = factory
    return seen


def _check_steady_state(tap, compiles_before, compiles_after, last_ts):
    """Compiles happened by the first window; the last third of the
    watermarks that closed windows in the stream compiled nothing, the
    batches between them included. Windows still open at ``last_ts`` are
    closed by the end-of-input watermark, which retires every remaining
    slice in one call and may meet a new pad tier: its compiles are
    reported, not refused."""
    stream = [c for c in tap["calls"] if c.watermark <= last_ts and c.fired]
    flush = [c for c in tap["calls"] if c.watermark > last_ts]
    require(len(stream) >= 3, f"only {len(stream)} in-stream fires")
    by_first = stream[0].compiles_at_exit - compiles_before
    require(by_first > 0,
            "no compile seen by the first window: the sentinel is blind")
    tail = max(len(stream) // 3, 1)
    stream_end = flush[0].compiles_at_entry if flush else compiles_after
    in_tail = stream_end - stream[-tail].compiles_at_entry
    require(in_tail == 0,
            f"{in_tail} XLA compile(s) in the last {tail} of "
            f"{len(stream)} in-stream fires")
    return {"fires_dispatched": sum(c.fired for c in tap["calls"]),
            "in_stream_firing_watermarks": len(stream),
            "compiles_by_first_window": by_first,
            "compiles_in_last_third_of_stream": in_tail,
            "compiles_in_end_of_input_flush": compiles_after - stream_end}


def _devices_of(arrays):
    return {d for a in arrays for d in a.devices()}


def _check_on_device(devs, what, platform, num_devices=1):
    require({d.platform for d in devs} == {platform},
            f"{what} on {sorted(map(str, devs))}, wanted platform {platform}")
    require(len(devs) == num_devices,
            f"{what} span {len(devs)} device(s), wanted {num_devices}")
    return sorted(str(d) for d in devs)


def _check_native():
    from flink_tpu import native

    built = native.build_all()
    require(all(built.values()), f"native build failed: {built}")
    require(native.native_fallbacks() == 0,
            f"{native.native_fallbacks()} native->Python fallback(s)")


def _execute_and_check(env, tap, job_name, platform, chips, last_ts):
    """``env.execute()``, then what every job is held to: state and fire
    outputs on ``chips`` devices of ``platform``, no native fallback, a
    steady state that compiles nothing. Returns the engine, its state
    arrays and the stats for the phase's line."""
    import jax

    from flink_tpu.observe.recompile_sentinel import compile_count

    c0 = compile_count()
    t0 = time.perf_counter()
    env.execute(job_name)
    wall = time.perf_counter() - t0
    c1 = compile_count()
    (op,) = tap["ops"]
    engine = op.windower
    accs = engine.accs if chips > 1 else engine.table.accs
    _check_native()
    return engine, accs, {
        "wall_seconds": wall, "engine": type(engine).__name__,
        "state_bytes": sum(a.nbytes for a in accs),
        "state_on": _check_on_device(_devices_of(accs), "state arrays",
                                     platform, chips),
        "fire_outputs_on": _check_on_device(
            tap["fire_devices"], "fire outputs", platform, chips),
        "peak_bytes_in_use": _peak_bytes(jax.devices()[:chips]),
        **_check_steady_state(tap, c0, c1, last_ts)}


def _poll_all(source, chunk=1 << 20):
    """The whole stream of a fresh source, as the reference reads it."""
    source.open(0, 1)
    while True:
        batch = source.poll_batch(chunk)
        if batch is None:
            return
        yield batch


# ------------------------------------------------------------ the Q5 job


def q5_reference(seed, events, num_auctions, rate, size_ms, slide_ms):
    """Plain NumPy Q5: count per (auction, slide slice), windows as sums
    of ``size/slide`` consecutive slices, arg-max with ties. Returns the
    set of ``(window_end, auction, count)`` winner rows."""
    import numpy as np

    from flink_tpu.benchmarks.nexmark import BidSource

    k = size_ms // slide_ms
    n_slices = ((events - 1) * 1000 // rate) // slide_ms + 1
    cells = [(np.asarray(b.timestamps) // slide_ms) * num_auctions
             + np.asarray(b["auction"])
             for b in _poll_all(BidSource(
                 events, num_auctions=num_auctions,
                 events_per_second_of_eventtime=rate, seed=seed))]
    per_slice = np.bincount(
        np.concatenate(cells), minlength=(n_slices + k) * num_auctions
    ).reshape(n_slices + k, num_auctions)
    cum = np.cumsum(per_slice, axis=0)
    want = set()
    for j in range(n_slices + k - 1):  # window = slices j-k+1 .. j
        counts = cum[j] - (cum[j - k] if j >= k else 0)
        best = counts.max()
        if best > 0:
            for a in np.flatnonzero(counts == best):
                want.add(((j + 1) * slide_ms, int(a), int(best)))
    return want


def run_q5(seed, events, num_auctions, rate, capacity, batch,
           platform, chips=1):
    """Nexmark Q5 hot items (BASELINE.json config 2): HOP 10 s / 2 s,
    COUNT with the fused top-k, against :func:`q5_reference`.
    ``chips`` > 1 submits at ``parallelism.default=chips`` and holds the
    operator to the mesh engine with the in-program exchange."""
    from flink_tpu import Configuration, StreamExecutionEnvironment
    from flink_tpu.benchmarks.nexmark import BidSource, build_q5
    from flink_tpu.connectors.sinks import CollectSink

    conf = {"execution.micro-batch.size": batch,
            "state.slot-table.capacity": capacity}
    if chips > 1:
        conf["parallelism.default"] = chips
    env = StreamExecutionEnvironment(Configuration(conf))
    sink = CollectSink()
    winners = build_q5(
        env, BidSource(events, num_auctions=num_auctions,
                       events_per_second_of_eventtime=rate, seed=seed),
        size_ms=10_000, slide_ms=2_000, device_top_k=16)
    # build_q5 = window aggregate -> arg-max map: tap the aggregate
    tap = _tap_window_operator(winners.transformation.inputs[0])
    winners.sink_to(sink)
    engine, accs, stats = _execute_and_check(
        env, tap, "chip-smoke-q5", platform, chips,
        (events - 1) * 1000 // rate)
    if chips > 1:
        require(type(engine).__name__ == "MeshWindowEngine", type(engine))
        require(engine.mesh.devices.size == chips, engine.mesh)
        require(engine.shuffle_mode == "device", engine.shuffle_mode)
        for a in accs:
            shards = a.addressable_shards
            require(a.shape == (chips, capacity), a.shape)
            require(len({s.device for s in shards}) == chips
                    and all(s.data.shape == (1, capacity) for s in shards),
                    f"not one [1, {capacity}] shard per device: {shards}")

    got_rows = sink.result()
    got = set(zip(got_rows["window_end"].tolist(),
                  got_rows["auction"].tolist(),
                  got_rows["count"].tolist()))
    want = q5_reference(seed, events, num_auctions, rate, 10_000, 2_000)
    require(len(got_rows) == len(got), "duplicate winner rows")
    require(got == want,
            f"Q5 differs from the reference: {len(got - want)} unexpected, "
            f"{len(want - got)} missing, e.g. {sorted(got ^ want)[:4]}")
    return {"events": events,
            "windows_fired": len({w for w, _, _ in got}),
            "winner_rows": len(got), **stats}


# ------------------------------------------------- the keyed-state job


def run_keyed_state(seed, events, num_keys, rate, capacity, batch,
                    platform):
    """State at a cardinality users call real (BASELINE.json config 5's
    key space on the tumbling hot path): 5 s tumbling ``sum`` over
    ``num_keys`` distinct keys, against a NumPy bincount reference."""
    import numpy as np

    from flink_tpu import Configuration, StreamExecutionEnvironment
    from flink_tpu.connectors.sinks import CollectSink
    from flink_tpu.connectors.sources import DataGenSource
    from flink_tpu.runtime.watermarks import WatermarkStrategy
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    size_ms = 5_000
    last_ts = (events - 1) * 1000 // rate

    def source():
        return DataGenSource(events, num_keys,
                             events_per_second_of_eventtime=rate,
                             seed=seed)

    env = StreamExecutionEnvironment(Configuration({
        "execution.micro-batch.size": batch,
        "state.slot-table.capacity": capacity}))
    sink = CollectSink()
    sums = (env.from_source(
        source(), WatermarkStrategy.for_bounded_out_of_orderness(0))
        .key_by("key")
        .window(TumblingEventTimeWindows.of(size_ms))
        .sum("value"))
    tap = _tap_window_operator(sums.transformation)
    sums.sink_to(sink)
    _, _, stats = _execute_and_check(
        env, tap, "chip-smoke-keyed-state", platform, 1, last_ts)

    # reference: per (window, key) cell, the f64 sum and the presence
    n_cells = (last_ts // size_ms + 1) * num_keys
    batches = list(_poll_all(source()))
    cell = np.concatenate([
        (np.asarray(b.timestamps) // size_ms) * num_keys
        + np.asarray(b["key"]) for b in batches])
    want_sum = np.bincount(
        cell, weights=np.concatenate([b["value"] for b in batches]),
        minlength=n_cells)
    live = np.flatnonzero(np.bincount(cell, minlength=n_cells))
    rows = sink.result()
    got_cell = ((np.asarray(rows["window_end"]) // size_ms - 1) * num_keys
                + np.asarray(rows["key"]))
    require(len(got_cell) == len(live),
            f"{len(got_cell)} (key, window) rows, reference has {len(live)}")
    order = np.argsort(got_cell, kind="stable")
    require(np.array_equal(got_cell[order], live),
            "keys/windows differ from the reference")
    got_sum = np.asarray(rows["sum_value"], dtype=np.float64)[order]
    np.testing.assert_allclose(got_sum, want_sum[live], rtol=1e-4,
                               atol=1e-6)
    per_window = np.bincount(live // num_keys)
    return {"events": events, "distinct_keys": num_keys,
            "windows_fired": len(per_window),
            "result_rows": len(live),
            "max_live_slots_in_a_window": int(per_window.max()), **stats}


# -------------------------------------------------- the four-chip path


def run_rank_parity(num_dests, n, platform):
    """The exchange-rank kernel itself on this backend: compiled by
    Mosaic off the CPU (never interpreted), equal to the XLA rank."""
    import jax
    import numpy as np

    from flink_tpu.stateplane.rank import build_exchange_rank

    d = np.random.default_rng(n).integers(
        -1, num_dests + 1, size=n).astype(np.int32)
    width = n
    got = {}
    for backend in ("xla", "pallas"):
        program = build_exchange_rank(num_dests, backend)
        got[backend] = np.asarray(program(d, width))
        if backend == "pallas" and platform != "cpu":
            text = program.lower(d, width).compile().as_text()
            require("tpu_custom_call" in text,
                    "pallas rank did not lower to a Mosaic kernel")
    require(np.array_equal(got["xla"], got["pallas"]),
            "pallas rank differs from xla rank")
    return {"records": n, "num_dests": num_dests,
            "interpreted": jax.default_backend() == "cpu"}


def run_q5_mesh(seed, events, num_auctions, rate, capacity, batch,
                platform, chips):
    """Q5 at ``parallelism.default=chips`` once per exchange-rank
    backend; each run is held to the reference, the mesh engine, the
    device exchange and one state shard per device."""
    from flink_tpu.stateplane import backend_scope
    from flink_tpu.tenancy.program_cache import PROGRAM_CACHE

    out = {"rank_kernel": run_rank_parity(chips, 1 << 16, platform)}
    for backend in ("xla", "pallas"):
        with backend_scope("exchange-rank", backend):
            out[backend] = run_q5(seed, events, num_auctions, rate,
                                  capacity, batch, platform, chips=chips)
        require(any(kind == "exchange-scatter" and key[-1] == backend
                    for kind, key in PROGRAM_CACHE.programs),
                f"no exchange-scatter program was built with {backend} rank")
    return out


# ------------------------------------------------------------------ main

#: the sizes a deployment states (ISSUE 22 / BASELINE.json configs 2, 5).
#: Q5's micro-batch spans 1.3 s of event time, under the 2 s slide, so a
#: watermark closes at most one window and retires at most one slice:
#: every program meets its pad tiers in the first windows.
Q5 = dict(events=8_388_608, num_auctions=100_000, rate=100_000,
          capacity=1 << 22, batch=1 << 17)
KEYED_STATE = dict(events=20_000_000, num_keys=10_000_000, rate=1_000_000,
                   capacity=1 << 24, batch=1 << 18)


def _watchdog(out, seconds, device):
    """A run that outlives its limit leaves with ``ok: false`` and the
    stacks on standard error, instead of being cut without a line."""

    def expire():
        import faulthandler

        faulthandler.dump_traceback(file=sys.stderr)
        print(f"chip_smoke: not done after {seconds}s", file=sys.stderr)
        verdict(out, False, device[0])

    t = threading.Timer(seconds, expire)
    t.daemon = True
    t.start()


def main(argv=None):
    out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    device = [NO_DEVICE]
    try:
        ap = argparse.ArgumentParser()
        ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
        ap.add_argument("--seed", type=int, default=22)
        ap.add_argument("--deadline", type=float, default=1100.0,
                        help="seconds before the run gives up")
        args = ap.parse_args(argv)
        _watchdog(out, args.deadline, device)

        device[0] = jax_device()
        if device[0]["platform"] != "tpu":
            raise RuntimeError(
                f"chip_smoke needs a TPU, JAX gave {device[0]}")
        if device[0]["count"] != args.chips:
            raise RuntimeError(
                f"--chips {args.chips} but JAX shows "
                f"{device[0]['count']} device(s)")

        from flink_tpu import native
        from flink_tpu.platform import enable_compilation_cache

        enable_compilation_cache()
        cache_dir, entries = _cache_entries()
        report(out, phase="setup", device=device[0], seed=args.seed,
               compile_cache_dir=cache_dir, compile_cache_entries=entries,
               native=native.build_report())
        compiles = _Compiles()

        if args.chips == 1:
            phases = [
                ("q5_hot_items", run_q5, Q5),
                ("keyed_state_10m", run_keyed_state, KEYED_STATE)]
        else:
            phases = [("q5_mesh", run_q5_mesh, dict(Q5, chips=args.chips))]
        for name, fn, sizes in phases:
            before = compiles.snapshot()
            stats = fn(args.seed, platform="tpu", **sizes)
            report(out, phase=name, ok=True, **stats,
                   **_delta(compiles.snapshot(), before),
                   compile_cache_entries=_cache_entries()[1])
    except BaseException:
        import traceback

        traceback.print_exc(file=sys.stderr)
        verdict(out, False, device[0])
    verdict(out, True, device[0])


if __name__ == "__main__":
    main()
