"""What the harness observes of the program, never altering it: the
window operator the executor builds, XLA compiles, device memory, the
native planes. Copies of the probes ``chip_smoke.py`` proved on the chip.
"""


def require(cond, why):
    """A check that holds under ``python -O`` too."""
    if not cond:
        raise AssertionError(str(why))


def device_info():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def compile_count():
    from flink_tpu.observe import recompile_sentinel as rs

    rs.install()
    return rs.compile_count()


def peak_bytes(chips):
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else 0


def host_usage():
    """CPU seconds the calling thread and the whole process have had so
    far. Read before and after the window on the thread that runs the
    operators: wall well above the thread's CPU seconds says it was kept
    waiting (for the device, for input, for its core), not working more.
    (The chip's machine shows no ``/proc/stat``, load or context switches:
    they read 0 there, my chip run, PR 25, so they are not recorded.)"""
    import time

    return {"thread_cpu_s": time.thread_time(),
            "process_cpu_s": time.process_time()}


def check_native():
    from flink_tpu import native

    built = native.build_all()
    require(all(built.values()), f"native build failed: {built}")
    require(native.native_fallbacks() == 0,
            f"{native.native_fallbacks()} native->Python fallback(s)")


def tap_window_operator(t, span=None):
    """Observe the window operator the executor builds for transformation
    ``t``: keeps the instance, counts the windows its ``on_watermark``
    calls fired and notes the devices each dispatched fire output sat on.
    With ``span`` (a traced run) ``process_batch`` and ``on_watermark``
    also run inside a host span of the profiler's trace."""
    seen = {"ops": [], "fire_devices": set(), "fires": 0}
    make = t.operator_factory

    def factory():
        op = make()
        seen["ops"].append(op)
        opened = op.open

        def open_and_tap(ctx):
            opened(ctx)
            fire = op.windower.on_watermark

            def tapped(watermark, *args, **kwargs):
                if span is None:
                    fired = fire(watermark, *args, **kwargs)
                else:
                    with span("on_watermark"):
                        fired = fire(watermark, *args, **kwargs)
                for f in fired:
                    for a in getattr(f, "arrays", ()):
                        seen["fire_devices"] |= set(a.devices())
                seen["fires"] += len(fired)
                return fired

            op.windower.on_watermark = tapped
            if span is not None:
                process = op.windower.process_batch

                def spanned(*args, **kwargs):
                    with span("process_batch"):
                        return process(*args, **kwargs)

                op.windower.process_batch = spanned

        op.open = open_and_tap
        return op

    t.operator_factory = factory
    return seen


def state_arrays(engine):
    """The device arrays that hold the window state of ``engine``."""
    return engine.accs if hasattr(engine, "accs") else engine.table.accs


def check_placement(tap, expect, platform, chips):
    """State and fire outputs on ``chips`` devices of ``platform``, the
    engine the configuration expects, no native fallback."""
    (op,) = tap["ops"]
    engine = op.windower
    require(type(engine).__name__ == expect["engine"],
            f"engine {type(engine).__name__}, wanted {expect['engine']}")
    accs = state_arrays(engine)
    for what, devs in (
            ("state arrays", {d for a in accs for d in a.devices()}),
            ("fire outputs", tap["fire_devices"])):
        require({d.platform for d in devs} == {platform},
                f"{what} on {sorted(map(str, devs))}, wanted {platform}")
        require(len(devs) == chips,
                f"{what} span {len(devs)} device(s), wanted {chips}")
    for name, want in expect.get("engine_attributes", {}).items():
        require(str(getattr(engine, name)) == str(want),
                f"engine.{name} is {getattr(engine, name)}, wanted {want}")
    if "state_shape" in expect:
        for a in accs:
            require(list(a.shape) == list(expect["state_shape"]),
                    f"state {a.shape}, wanted {expect['state_shape']}")
            require(len({s.device for s in a.addressable_shards}) == chips,
                    "not one state shard per device")
    check_native()
    return {"engine": type(engine).__name__,
            "state_bytes": int(sum(a.nbytes for a in accs))}
