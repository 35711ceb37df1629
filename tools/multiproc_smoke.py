#!/usr/bin/env python
"""Multi-process pod smoke: 2 REAL CPU processes, one key-group space.

The ROADMAP item-2 acceptance oracle, executable on any dev box: two
processes (``jax.distributed.initialize`` + gloo CPU collectives), each
owning half the key-group space with its own session-metadata plane,
spill tier and per-range checkpoint units, exchange records over the
DCN axis of the process-spanning mesh ON DEVICE
(``parallel/pod.PodDataPlane``) and run the mesh_sessions shape.

FAILS on any of:

- output divergence: the union of the two processes' committed windows
  must be BIT-IDENTICAL to the single-process run of the same stream,
- steady-state compiles: the measured rep (fresh engines + fresh pod
  plane on the warm program cache) must compile NOTHING,
- a vacuous run: 0 rows crossed a process boundary on the device plane,
- the chaos leg: kill process 1 mid-stream — the survivor must restore
  ONLY the dead host's key-group ranges from its checkpoint units,
  replay no more than the per-host bound, and finish bit-identical.

Also emits the ``mesh_sessions_2proc`` bench numbers (aggregate ev/s +
scaling vs the same-box 1-process run) — honest caveat: on a 1-core CI
box two processes time-share one clock, so the aggregate measures
pod-protocol overhead, not the pod speedup a multi-core/multi-host box
shows.

    JAX_PLATFORMS=cpu python tools/multiproc_smoke.py
    MP_SMOKE_RECORDS=$((1<<17)) ... # scale knobs
"""
import json
import os
import socket
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

GAP = 40
SPAN = 80
MAXP = 128
HOSTS, LOCAL = 2, 4

RECORDS = int(os.environ.get("MP_SMOKE_RECORDS", str(1 << 16)))
BATCH = int(os.environ.get("MP_SMOKE_BATCH", "4096"))
KEYS = int(os.environ.get("MP_SMOKE_KEYS", str(max(RECORDS // 3, 64))))
SLOTS = int(os.environ.get("MP_SMOKE_SLOTS", "0"))
SEED = int(os.environ.get("MP_SMOKE_SEED", "23"))
KILL_AT = int(os.environ.get("MP_SMOKE_KILL_AT", "0"))  # child flag
CKPT_EVERY = int(os.environ.get("MP_SMOKE_CKPT_EVERY", "4"))
FINAL_WM = 1 << 60


def n_batches() -> int:
    return -(-RECORDS // BATCH)


def make_batch(b: int):
    """Global batch ``b`` — regenerable by ANY process from the seed
    (the replay path depends on this: a survivor rebuilds the dead
    host's range from the stream, not from the dead host)."""
    import numpy as np

    rng = np.random.default_rng(SEED * 1_000_003 + b)
    n = min(BATCH, RECORDS - b * BATCH)
    keys = rng.integers(0, KEYS, n).astype(np.int64)
    vals = rng.integers(0, 1000, n).astype(np.float32)
    ts = rng.integers(b * SPAN, b * SPAN + 60, n).astype(np.int64)
    return keys, vals, ts, (b - 1) * SPAN


def _keyed(keys, vals, ts):
    import numpy as np

    from flink_tpu.core.records import (
        KEY_ID_FIELD,
        TIMESTAMP_FIELD,
        RecordBatch,
    )

    return RecordBatch({
        KEY_ID_FIELD: np.asarray(keys, dtype=np.int64),
        "v": np.asarray(vals, dtype=np.float32),
        TIMESTAMP_FIELD: np.asarray(ts, dtype=np.int64)})


def _collect(batches, into):
    from flink_tpu.core.records import KEY_ID_FIELD

    for b in batches:
        for r in b.to_rows():
            into[(int(r[KEY_ID_FIELD]), int(r["window_start"]),
                  int(r["window_end"]))] = float(r["sum_v"])


def _dump(path, committed, **extra):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"committed": [[k[0], k[1], k[2], v]
                                 for k, v in sorted(committed.items())],
                   **extra}, f)
    os.replace(tmp, path)


def _load_committed(path):
    with open(path) as f:
        d = json.load(f)
    return {(k, a, b): v for k, a, b, v in d["committed"]}, d


def _mk_engine(key_group_range=None):
    import jax

    from flink_tpu.parallel.mesh import make_mesh
    from flink_tpu.parallel.sharded_sessions import MeshSessionEngine
    from flink_tpu.windowing.aggregates import SumAggregate

    return MeshSessionEngine(
        GAP, SumAggregate("v"),
        make_mesh(devices=jax.local_devices()),
        capacity_per_shard=1 << 14, max_device_slots=SLOTS,
        max_parallelism=MAXP, key_group_range=key_group_range,
        max_dispatch_ahead=2)


# --------------------------------------------------------------- children


def run_single(out_path: str) -> None:
    """1-process baseline: the full stream through one engine over the
    same per-process device count — the smoke's oracle AND the scaling
    row's denominator."""
    from flink_tpu.observe import compile_count

    def rep(commit: bool):
        committed = {}
        eng = _mk_engine()
        for b in range(n_batches()):
            keys, vals, ts, wm = make_batch(b)
            eng.process_batch(_keyed(keys, vals, ts))
            _collect(eng.on_watermark(wm), committed)
        _collect(eng.on_watermark(FINAL_WM), committed)
        return committed

    rep(False)                      # warmup: compiles + tier walk
    c0 = compile_count()
    t0 = time.perf_counter()
    committed = rep(True)           # measured: fresh engine, warm cache
    wall = time.perf_counter() - t0
    _dump(out_path, committed, wall_s=wall, events=RECORDS,
          compiles_measured=compile_count() - c0)


def run_pod(pid: int, port: int, out_path: str,
            ckpt_root: str) -> None:
    """One pod process: owns ``host_key_group_ranges[pid]``, exchanges
    the rest over the DCN axis, commits per checkpoint epoch. With
    KILL_AT > 0 this is the chaos leg: process 1 dies after batch
    KILL_AT; process 0 evacuates the dead host's ranges."""
    import numpy as np

    from flink_tpu.parallel.mesh import (
        HostTopology,
        initialize_distributed,
    )

    initialize_distributed(f"localhost:{port}", HOSTS, pid)

    from flink_tpu.checkpoint.sharded import ShardedCheckpointStorage
    from flink_tpu.observe import compile_count
    from flink_tpu.parallel.pod import PodDataPlane
    from flink_tpu.state.keygroups import (
        assign_key_groups,
        host_key_group_ranges,
        host_of_key_group,
    )

    topo = HostTopology(HOSTS, LOCAL)
    ranges = host_key_group_ranges(HOSTS, LOCAL, MAXP)
    my_range = ranges[pid]
    half = lambda b, n: (slice(0, n // 2) if pid == 0  # noqa: E731
                         else slice(n // 2, n))

    def owners_of(keys):
        return host_of_key_group(
            assign_key_groups(keys, MAXP), HOSTS, LOCAL, MAXP)

    progress = os.path.join(ckpt_root, f"host-{pid}.progress")
    tombstone = os.path.join(ckpt_root, "host-1.dead")
    storage = ShardedCheckpointStorage(
        os.path.join(ckpt_root, f"host-{pid}"))

    def rep(commit: bool, chaos: bool):
        committed, epoch = {}, {}
        eng = _mk_engine(my_range)
        plane = PodDataPlane(
            topo, dtypes=[np.int64, np.int64, np.float32],
            max_parallelism=MAXP)
        evac = None            # survivor's engine for the dead range
        cid = 0
        replayed = 0
        restored_units = 0
        for b in range(n_batches()):
            keys, vals, ts, wm = make_batch(b)
            if chaos and b > KILL_AT:
                if pid == 1:
                    return committed, plane, 0, 0
                if evac is None:
                    # the death notification (the deterministic chaos
                    # schedule stands in for the heartbeat timeout):
                    # restore ONLY the dead host's ranges from ITS
                    # checkpoint units, replay only its records
                    for _ in range(200):
                        if os.path.exists(tombstone):
                            break
                        time.sleep(0.05)
                    assert os.path.exists(tombstone), \
                        "peer never wrote its death marker"
                    dead_storage = ShardedCheckpointStorage(
                        os.path.join(ckpt_root, "host-1"))
                    found = dead_storage.read_all_units_with_fallback()
                    evac = _mk_engine(ranges[1])
                    if found is None:
                        unit_pos = 0
                    else:
                        _newest, units, _skipped = found
                        for r, _s, _p in units:
                            assert ranges[1][0] <= r[0] \
                                and r[1] <= ranges[1][1], \
                                f"unit {r} outside the dead range"
                        evac.restore(evac.merge_unit_snapshots(
                            [s for _r, s, _p in units]))
                        restored_units = len(units)
                        unit_pos = min(p for _r, _s, p in units)
                    # the dead host's committed output survives in its
                    # committed file; everything after its last
                    # checkpoint replays here (uncommitted epoch was
                    # rolled back with the process)
                    for rb in range(unit_pos, KILL_AT + 1):
                        rk, rv, rt, rwm = make_batch(rb)
                        mask = owners_of(rk) == 1
                        if mask.any():
                            evac.process_batch(_keyed(
                                rk[mask], rv[mask], rt[mask]))
                            replayed += int(mask.sum())
                        _collect(evac.on_watermark(rwm), epoch)
                # post-evacuation: the survivor owns everything — it
                # regenerates the FULL batch and routes host-side (the
                # DCN plane died with the peer)
                own = owners_of(keys)
                m0, m1 = own == 0, own == 1
                if m0.any():
                    eng.process_batch(_keyed(keys[m0], vals[m0],
                                             ts[m0]))
                if m1.any():
                    evac.process_batch(_keyed(keys[m1], vals[m1],
                                              ts[m1]))
                _collect(eng.on_watermark(wm), epoch)
                _collect(evac.on_watermark(wm), epoch)
            else:
                n = len(keys)
                sl = half(b, n)
                sub_k, sub_v, sub_t = keys[sl], vals[sl], ts[sl]
                # both processes regenerate the full batch, so the
                # chunk bound is deterministic — no agreement
                # collective per batch
                arrivals = plane.exchange(
                    owners_of(sub_k), [sub_k, sub_t, sub_v],
                    chunk_bound=-(-(n - n // 2) // LOCAL))
                ak, at, av = arrivals[plane.my_host]
                if len(ak):
                    eng.process_batch(_keyed(ak, av, at))
                _collect(eng.on_watermark(wm), epoch)
                with open(progress + ".tmp", "w") as f:
                    f.write(str(b))
                os.replace(progress + ".tmp", progress)
            if commit and (b + 1) % CKPT_EVERY == 0:
                cid += 1
                units = eng.snapshot_sharded()
                storage.write_checkpoint(
                    cid, f"pod-host-{pid}", units,
                    positions={r: b + 1 for r in units})
                committed.update(epoch)
                epoch = {}
                _dump(out_path, committed, final=False)
            if chaos and pid == 1 and b == KILL_AT:
                # die "mid-stream": after the batch's collective, with
                # an uncommitted epoch in flight — write the death
                # marker (the cluster manager's notification) and
                # vanish without a final flush
                with open(tombstone, "w") as f:
                    f.write(str(b))
                _dump(out_path, committed, final=False,
                      died_at=b)
                os._exit(0)
        _collect(eng.on_watermark(FINAL_WM), epoch)
        if evac is not None:
            _collect(evac.on_watermark(FINAL_WM), epoch)
        committed.update(epoch)
        return committed, plane, replayed, restored_units

    if KILL_AT:
        t0 = time.perf_counter()
        committed, plane, replayed, restored_units = rep(
            commit=True, chaos=True)
        wall = time.perf_counter() - t0
        _dump(out_path, committed, final=True, wall_s=wall,
              events=RECORDS, replayed=replayed,
              restored_units=restored_units,
              cross_rows=plane.rows_cross_host,
              intra_rows=plane.rows_intra_host)
        # the peer is dead: jax.distributed's shutdown barrier can
        # only fail (heartbeat timeout -> abort) — results are on
        # disk, leave without running it
        os._exit(0)

    rep(commit=False, chaos=False)  # warmup: compiles + tier walk
    c0 = compile_count()
    t0 = time.perf_counter()
    committed, plane, _, _ = rep(commit=True, chaos=False)
    wall = time.perf_counter() - t0
    _dump(out_path, committed, final=True, wall_s=wall,
          events=RECORDS,
          compiles_measured=compile_count() - c0,
          cross_rows=plane.rows_cross_host,
          intra_rows=plane.rows_intra_host)


# ----------------------------------------------------------------- parent


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn(role, workdir, extra_env=None, **kw):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("MP_SMOKE_CHILD_XLA", "")
        + " --xla_force_host_platform_device_count="
        + str(LOCAL)).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["MP_SMOKE_ROLE"] = role
    for k, v in kw.items():
        env[k.upper()] = str(v)
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        env=env, cwd=workdir,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)


def _wait(procs, names, timeout=900):
    outs = {}
    deadline = time.time() + timeout
    for p, name in zip(procs, names):
        try:
            out, _ = p.communicate(timeout=max(deadline - time.time(),
                                               1))
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            raise SystemExit(
                f"MULTIPROC SMOKE: {name} timed out\n"
                + out.decode()[-2000:])
        outs[name] = out.decode()
        if p.returncode != 0:
            raise SystemExit(
                f"MULTIPROC SMOKE: {name} failed "
                f"(rc={p.returncode})\n" + outs[name][-2000:])
    return outs


def _merge_committed(parts):
    merged = {}
    for part in parts:
        for k, v in part.items():
            if k in merged and merged[k] != v:
                raise SystemExit(
                    f"MULTIPROC SMOKE: conflicting committed cell {k}:"
                    f" {merged[k]} vs {v}")
            merged[k] = v
    return merged


def main() -> int:
    import tempfile

    workdir = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    tmp = tempfile.mkdtemp(prefix="mp_smoke_")

    # ---- 1-process baseline (oracle + scaling denominator) ----
    single_out = os.path.join(tmp, "single.json")
    _wait([_spawn("single", workdir, mp_smoke_out=single_out)],
          ["single"])
    oracle, single_meta = _load_committed(single_out)
    if single_meta["compiles_measured"] != 0:
        raise SystemExit(
            "MULTIPROC SMOKE: single-process measured rep compiled "
            f"{single_meta['compiles_measured']} programs")

    # ---- 2-process scaling phase ----
    port = _free_port()
    outs = [os.path.join(tmp, f"pod-{i}.json") for i in range(HOSTS)]
    ck = os.path.join(tmp, "ck-scale")
    os.makedirs(ck, exist_ok=True)
    procs = [
        _spawn("pod", workdir, mp_smoke_out=outs[i],
               mp_smoke_pid=i, mp_smoke_port=port,
               mp_smoke_ckpt=ck)
        for i in range(HOSTS)]
    _wait(procs, [f"pod-{i}" for i in range(HOSTS)])
    parts, metas = zip(*[_load_committed(o) for o in outs])
    merged = _merge_committed(parts)
    if merged != oracle:
        extra = set(merged) - set(oracle)
        missing = set(oracle) - set(merged)
        wrong = [k for k in merged
                 if k in oracle and merged[k] != oracle[k]]
        raise SystemExit(
            "MULTIPROC SMOKE: 2-process output DIVERGED from the "
            f"single-process run ({len(missing)} missing, "
            f"{len(extra)} extra, {len(wrong)} wrong; e.g. "
            f"{(list(missing) + list(extra) + wrong)[:3]})")
    cross = sum(m["cross_rows"] for m in metas)
    intra = sum(m["intra_rows"] for m in metas)
    if cross == 0:
        raise SystemExit(
            "MULTIPROC SMOKE: vacuous — 0 rows crossed a process "
            "boundary on the device plane")
    compiles = sum(m["compiles_measured"] for m in metas)
    if compiles != 0:
        raise SystemExit(
            f"MULTIPROC SMOKE: measured rep compiled {compiles} "
            "programs (steady state must be 0)")
    wall_2p = max(m["wall_s"] for m in metas)
    ev_s_2p = RECORDS / wall_2p
    ev_s_1p = RECORDS / single_meta["wall_s"]
    scaling = ev_s_2p / ev_s_1p
    # the near-linear target (ROADMAP item 2) is gateable only where 2
    # processes get 2 clocks: a 1-core CI box time-shares them and
    # measures protocol overhead, not pod speedup — so
    # the gate ARMS ITSELF when the affinity mask grants >= 2 CPUs
    # (1.4x default: two clocks minus the DCN/ICI protocol tax), and
    # stays env-overridable both ways (0 disarms, higher tightens)
    default_gate = ("1.4" if len(os.sched_getaffinity(0)) >= 2
                    else "0")
    min_scaling = float(os.environ.get("MP_SMOKE_MIN_SCALING",
                                       default_gate))
    if min_scaling and scaling < min_scaling:
        raise SystemExit(
            f"MULTIPROC SMOKE: scaling {scaling:.2f}x under the "
            f"{min_scaling}x gate")

    # ---- chaos phase: kill process 1 mid-stream ----
    port = _free_port()
    kill_at = max(n_batches() * 2 // 3, CKPT_EVERY + 1)
    if kill_at >= n_batches() - 1:
        raise SystemExit(
            f"MULTIPROC SMOKE: shape too small — {n_batches()} "
            f"batches cannot host a mid-stream kill at {kill_at} "
            "(raise MP_SMOKE_RECORDS or lower MP_SMOKE_BATCH)")
    ck = os.path.join(tmp, "ck-chaos")
    os.makedirs(ck, exist_ok=True)
    outs_c = [os.path.join(tmp, f"chaos-{i}.json")
              for i in range(HOSTS)]
    procs = [
        _spawn("pod", workdir, mp_smoke_out=outs_c[i],
               mp_smoke_pid=i, mp_smoke_port=port,
               mp_smoke_ckpt=ck, mp_smoke_kill_at=kill_at)
        for i in range(HOSTS)]
    _wait(procs, [f"chaos-{i}" for i in range(HOSTS)])
    dead_part, dead_meta = _load_committed(outs_c[1])
    surv_part, surv_meta = _load_committed(outs_c[0])
    merged_c = _merge_committed([dead_part, surv_part])
    if merged_c != oracle:
        missing = set(oracle) - set(merged_c)
        extra = set(merged_c) - set(oracle)
        wrong = [k for k in merged_c
                 if k in oracle and merged_c[k] != oracle[k]]
        raise SystemExit(
            "MULTIPROC SMOKE: chaos output DIVERGED "
            f"({len(missing)} missing, {len(extra)} extra, "
            f"{len(wrong)} wrong)")
    if surv_meta["restored_units"] < 1:
        raise SystemExit(
            "MULTIPROC SMOKE: the survivor restored no checkpoint "
            "units — the dead host's state was rebuilt from nothing")
    if not (0 < surv_meta["replayed"] <= RECORDS // 2):
        raise SystemExit(
            f"MULTIPROC SMOKE: replay {surv_meta['replayed']} outside "
            f"the per-host bound (0, {RECORDS // 2}]")

    row = {
        "metric": "mesh_sessions_2proc_events_per_s",
        "value": round(ev_s_2p, 1),
        "single_proc_events_per_s": round(ev_s_1p, 1),
        "scaling_x": round(scaling, 3),
        "records": RECORDS,
        "cross_host_rows": cross,
        "intra_host_rows": intra,
        "chaos_replayed": surv_meta["replayed"],
        "chaos_restored_units": surv_meta["restored_units"],
        "chaos_recovery_bound": RECORDS // 2,
        "shape": (f"{RECORDS:,} records, 2 processes x {LOCAL} "
                  f"devices, sessions gap {GAP}; kill-1-of-2 "
                  "scenario bit-identical"),
    }
    print(json.dumps(row))
    print(f"MULTIPROC SMOKE OK: 2-proc {ev_s_2p:,.0f} ev/s vs 1-proc "
          f"{ev_s_1p:,.0f} ev/s ({scaling:.2f}x), "
          f"{cross:,} cross-host rows on the device plane, 0 "
          f"steady-state compiles, chaos leg restored "
          f"{surv_meta['restored_units']} units / replayed "
          f"{surv_meta['replayed']:,} records, all bit-identical")
    return 0


if __name__ == "__main__":
    role = os.environ.get("MP_SMOKE_ROLE", "parent")
    if role == "single":
        run_single(os.environ["MP_SMOKE_OUT"])
    elif role == "pod":
        KILL_AT = int(os.environ.get("MP_SMOKE_KILL_AT", "0"))
        run_pod(int(os.environ["MP_SMOKE_PID"]),
                int(os.environ["MP_SMOKE_PORT"]),
                os.environ["MP_SMOKE_OUT"],
                os.environ["MP_SMOKE_CKPT"])
    else:
        sys.exit(main())
