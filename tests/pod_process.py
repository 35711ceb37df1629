"""One process of the two-process pod run in ``test_host_failover.py``.

Not a test module: ``TestTwoRealProcesses`` starts this file as a child
process, once per role, with JAX on the CPU and four virtual devices:

    python tests/pod_process.py single --out single.json
    python tests/pod_process.py pod --out pod-0.json --pid 0 \
        --port 12345 --ckpt <dir> [--kill-at N]

``single`` runs the whole stream through one mesh session engine (the
oracle). ``pod`` is one of two processes (``jax.distributed`` with gloo
CPU collectives), each owning half the key-group space and exchanging
records over the DCN axis of the process-spanning mesh on the device
(``parallel/pod.PodDataPlane``). With ``--kill-at`` process 1 dies after
that batch and process 0 restores only the dead host's key-group ranges
from its checkpoint units and replays only its records. Each child
writes its committed windows and counters to ``--out`` as JSON.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

GAP = 40
SPAN = 80
MAXP = 128
HOSTS, LOCAL = 2, 4
RECORDS = 1 << 16
BATCH = 4096
KEYS = RECORDS // 3
SEED = 23
CKPT_EVERY = 4
FINAL_WM = 1 << 60


def n_batches() -> int:
    return -(-RECORDS // BATCH)


def make_batch(b: int):
    """Global batch ``b``, regenerable by ANY process from the seed (the
    replay path depends on it: a survivor rebuilds the dead host's range
    from the stream, not from the dead host)."""
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


def load_committed(path):
    """``(committed {(key, start, end): sum}, the whole record)``."""
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
        capacity_per_shard=1 << 14, max_device_slots=0,
        max_parallelism=MAXP, key_group_range=key_group_range,
        max_dispatch_ahead=2)


def run_single(out_path: str) -> None:
    """The full stream through one engine over the same per-process
    device count: a warmup rep, then a measured rep on a fresh engine."""
    from flink_tpu.observe import compile_count

    def rep():
        committed = {}
        eng = _mk_engine()
        for b in range(n_batches()):
            keys, vals, ts, wm = make_batch(b)
            eng.process_batch(_keyed(keys, vals, ts))
            _collect(eng.on_watermark(wm), committed)
        _collect(eng.on_watermark(FINAL_WM), committed)
        return committed

    rep()
    c0 = compile_count()
    committed = rep()
    _dump(out_path, committed, compiles_measured=compile_count() - c0)


def run_pod(pid: int, port: int, out_path: str, ckpt_root: str,
            kill_at: int) -> None:
    """One pod process: owns ``host_key_group_ranges[pid]``, exchanges
    the rest over the DCN axis, commits per checkpoint epoch. With
    ``kill_at`` > 0, process 1 dies after batch ``kill_at`` and process
    0 evacuates the dead host's ranges."""
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

    def owners_of(keys):
        return host_of_key_group(
            assign_key_groups(keys, MAXP), HOSTS, LOCAL, MAXP)

    tombstone = os.path.join(ckpt_root, "host-1.dead")
    storage = ShardedCheckpointStorage(
        os.path.join(ckpt_root, f"host-{pid}"))

    def evacuate():
        """Restore ONLY the dead host's ranges from ITS checkpoint units
        and replay its records since them. Returns (engine, epoch rows,
        records replayed, units restored)."""
        for _ in range(200):  # the death notice (a heartbeat stand-in)
            if os.path.exists(tombstone):
                break
            time.sleep(0.05)
        assert os.path.exists(tombstone), \
            "peer never wrote its death marker"
        found = ShardedCheckpointStorage(
            os.path.join(ckpt_root, "host-1")
        ).read_all_units_with_fallback()
        evac = _mk_engine(ranges[1])
        unit_pos, restored = 0, 0
        if found is not None:
            _newest, units, _skipped = found
            for r, _s, _p in units:
                assert ranges[1][0] <= r[0] and r[1] <= ranges[1][1], \
                    f"unit {r} outside the dead range"
            evac.restore(evac.merge_unit_snapshots(
                [s for _r, s, _p in units]))
            restored = len(units)
            unit_pos = min(p for _r, _s, p in units)
        # the dead host's committed output survives in its file; its
        # uncommitted epoch died with it and replays here
        epoch, replayed = {}, 0
        for rb in range(unit_pos, kill_at + 1):
            rk, rv, rt, rwm = make_batch(rb)
            mask = owners_of(rk) == 1
            if mask.any():
                evac.process_batch(_keyed(rk[mask], rv[mask], rt[mask]))
                replayed += int(mask.sum())
            _collect(evac.on_watermark(rwm), epoch)
        return evac, epoch, replayed, restored

    def rep(commit: bool, chaos: bool):
        committed, epoch = {}, {}
        eng = _mk_engine(ranges[pid])
        plane = PodDataPlane(
            topo, dtypes=[np.int64, np.int64, np.float32],
            max_parallelism=MAXP)
        evac = None
        cid = replayed = restored = 0
        for b in range(n_batches()):
            keys, vals, ts, wm = make_batch(b)
            if chaos and b > kill_at:
                if pid == 1:
                    return committed, plane, 0, 0
                if evac is None:
                    evac, replay_epoch, replayed, restored = evacuate()
                    epoch.update(replay_epoch)
                # the survivor owns everything now: it routes the full
                # batch on the host (the DCN plane died with the peer)
                own = owners_of(keys)
                for owner, engine in ((0, eng), (1, evac)):
                    m = own == owner
                    if m.any():
                        engine.process_batch(_keyed(keys[m], vals[m],
                                                    ts[m]))
                _collect(eng.on_watermark(wm), epoch)
                _collect(evac.on_watermark(wm), epoch)
            else:
                n = len(keys)
                sl = slice(0, n // 2) if pid == 0 else slice(n // 2, n)
                sub_k, sub_v, sub_t = keys[sl], vals[sl], ts[sl]
                # both processes regenerate the full batch, so the chunk
                # bound is deterministic: no agreement collective
                arrivals = plane.exchange(
                    owners_of(sub_k), [sub_k, sub_t, sub_v],
                    chunk_bound=-(-(n - n // 2) // LOCAL))
                ak, at, av = arrivals[plane.my_host]
                if len(ak):
                    eng.process_batch(_keyed(ak, av, at))
                _collect(eng.on_watermark(wm), epoch)
            if commit and (b + 1) % CKPT_EVERY == 0:
                cid += 1
                units = eng.snapshot_sharded()
                storage.write_checkpoint(
                    cid, f"pod-host-{pid}", units,
                    positions={r: b + 1 for r in units})
                committed.update(epoch)
                epoch = {}
                _dump(out_path, committed)
            if chaos and pid == 1 and b == kill_at:
                # die mid-stream, after the batch's collective, with an
                # uncommitted epoch in flight and no final flush
                with open(tombstone, "w") as f:
                    f.write(str(b))
                _dump(out_path, committed, died_at=b)
                os._exit(0)
        _collect(eng.on_watermark(FINAL_WM), epoch)
        if evac is not None:
            _collect(evac.on_watermark(FINAL_WM), epoch)
        committed.update(epoch)
        return committed, plane, replayed, restored

    if kill_at:
        committed, plane, replayed, restored = rep(commit=True,
                                                   chaos=True)
        _dump(out_path, committed, replayed=replayed,
              restored_units=restored,
              cross_rows=plane.rows_cross_host)
        # the peer is dead: jax.distributed's shutdown barrier can only
        # fail, and the results are on disk
        os._exit(0)

    rep(commit=False, chaos=False)  # warmup: compiles + tier walk
    c0 = compile_count()
    committed, plane, _, _ = rep(commit=True, chaos=False)
    _dump(out_path, committed, compiles_measured=compile_count() - c0,
          cross_rows=plane.rows_cross_host)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("role", choices=["single", "pod"])
    ap.add_argument("--out", required=True)
    ap.add_argument("--pid", type=int, default=0)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--kill-at", type=int, default=0)
    args = ap.parse_args(argv)
    if args.role == "single":
        run_single(args.out)
    else:
        run_pod(args.pid, args.port, args.out, args.ckpt, args.kill_at)


if __name__ == "__main__":
    main()
