"""Host-granular failure domains: the watchdog's HOST escalation level,
multi-shard evacuation (``lose_shards``), and the end-to-end host-loss
failover — a lost HOST (one process's contiguous slice of shards) means
"lose k shard units, restore k units, replay one contiguous range",
bit-identical to the fault-free oracle and seed-deterministic.

Most cases run single-process on virtual topologies (2x2 / 2x4 over
CPU devices); ``TestTwoRealProcesses`` drives the same protocol across
REAL process boundaries (kill 1 of 2 processes, each child running
``tests/pod_process.py``).
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from tests import pod_process

from flink_tpu.chaos.harness import run_shard_loss_verify
from flink_tpu.chaos.injection import FaultPlan, FaultRule
from flink_tpu.parallel.mesh import HostTopology, make_mesh
from flink_tpu.runtime.watchdog import (
    DeviceWatchdog,
    HostFailedError,
    MeshStalledError,
    ShardFailedError,
)
from flink_tpu.windowing.aggregates import SumAggregate

GAP = 100
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _steps(n_steps=8, per_step=800, num_keys=3000, seed=17):
    rng = np.random.default_rng(seed)
    out = []
    for s in range(n_steps):
        keys = rng.integers(0, num_keys, per_step).astype(np.int64)
        vals = rng.random(per_step).astype(np.float32)
        ts = rng.integers(s * 80, s * 80 + 60, per_step).astype(np.int64)
        out.append((keys, vals, ts, (s - 1) * 80))
    return out


def _mk_session_engine(shards=8, slots=1024, topology=HostTopology(2, 4)):
    from flink_tpu.parallel.sharded_sessions import MeshSessionEngine

    return MeshSessionEngine(
        GAP, SumAggregate("v"), make_mesh(shards),
        capacity_per_shard=1 << 14, max_device_slots=slots,
        max_dispatch_ahead=2, host_topology=topology)


def _mk_session_oracle():
    from flink_tpu.windowing.sessions import SessionWindower

    return SessionWindower(GAP, SumAggregate("v"), capacity=1 << 15)


def _host_loss_plan(host=1, nth=6):
    return FaultPlan(rules=[
        FaultRule(pattern="host.lost", nth=nth, where={"host": host})])


# ----------------------------------------------------- watchdog ladder


class TestHostEscalation:
    def _wd(self, hosts=2, local=2, **kw):
        wd = DeviceWatchdog(hosts * local, **kw)
        wd.set_topology(HostTopology(hosts, local))
        return wd

    def test_uniform_one_host_streak_declares_the_host(self):
        t = [0.0]
        wd = self._wd(deadline_ms=10, max_misses=2,
                      clock=lambda: t[0])
        # per-shard sections: ONLY host 1's shards (2, 3) miss
        for _ in range(2):
            for p in (2, 3):
                with wd.section("op", shard=p):
                    t[0] += 0.05
            for p in (0, 1):
                with wd.section("op", shard=p):
                    t[0] += 0.001
        with pytest.raises(HostFailedError) as ei:
            wd.boundary_probe()
        assert ei.value.host == 1
        assert ei.value.shards == (2, 3)
        assert wd.quarantined == {2, 3}
        assert wd.hosts_declared_dead == 1

    def test_partial_host_streak_stays_shard_granular(self):
        t = [0.0]
        wd = self._wd(deadline_ms=10, max_misses=2,
                      clock=lambda: t[0])
        # only ONE of host 1's shards misses — a wedged chip, not a
        # lost process: the shard, not the host, is declared
        for _ in range(2):
            with wd.section("op", shard=3):
                t[0] += 0.05
            for p in (0, 1, 2):
                with wd.section("op", shard=p):
                    t[0] += 0.001
        with pytest.raises(ShardFailedError) as ei:
            wd.boundary_probe()
        assert not isinstance(ei.value, HostFailedError)
        assert ei.value.shard == 3
        assert wd.quarantined == {3}

    def test_streak_spilling_outside_one_host_stays_shard_granular(
            self):
        # shards 0, 1 AND 2 miss (host 0 fully + half of host 1):
        # mixed attribution contradicts the lost-process signature —
        # no host is declared, the first offender shard is
        t = [0.0]
        wd = self._wd(deadline_ms=10, max_misses=2,
                      clock=lambda: t[0])
        for _ in range(2):
            for p in (0, 1, 2):
                with wd.section("op", shard=p):
                    t[0] += 0.05
            with wd.section("op", shard=3):
                t[0] += 0.001
        with pytest.raises(ShardFailedError) as ei:
            wd.boundary_probe()
        assert not isinstance(ei.value, HostFailedError)
        assert wd.hosts_declared_dead == 0
        assert wd.quarantined == {ei.value.shard}

    def test_whole_mesh_streak_is_still_a_stall(self):
        # EVERY live shard misses: no host attribution either — the
        # honest escalation stays the whole-job MeshStalledError
        t = [0.0]
        wd = self._wd(deadline_ms=10, max_misses=2,
                      clock=lambda: t[0])
        for _ in range(2):
            with wd.section("op"):  # whole-mesh SPMD section
                t[0] += 0.05
        with pytest.raises(MeshStalledError):
            wd.boundary_probe()
        assert not wd.quarantined

    def test_rebind_to_new_size_clears_stale_topology(self):
        wd = self._wd()
        wd.rebind(3)  # survivors after a loss: 2x2 no longer applies
        assert wd._topology is None

    def test_set_topology_validates_coverage(self):
        wd = DeviceWatchdog(4)
        with pytest.raises(ValueError, match="does not cover"):
            wd.set_topology(HostTopology(2, 4))


# ------------------------------------------------------- evacuation


class TestLoseShards:
    def test_contiguity_enforced(self):
        eng = _mk_session_engine()
        with pytest.raises(ValueError, match="contiguous"):
            eng.lose_shards([1, 3])

    def test_whole_mesh_loss_refused(self):
        eng = _mk_session_engine()
        with pytest.raises(ValueError, match="whole mesh"):
            eng.lose_shards(list(range(8)))

    def test_host_slice_evacuates_in_one_pass(self):
        from tests.test_sessions import keyed_batch

        eng = _mk_session_engine()
        steps = _steps(n_steps=3)
        for keys, vals, ts, wm in steps:
            eng.process_batch(keyed_batch(keys, vals, ts))
            eng.on_watermark(wm)
        topo = eng.host_topology
        dead = list(topo.shards_of_host(1))
        ranges = eng.shard_key_groups()
        want = (ranges[dead[0]][0], ranges[dead[-1]][1])
        g0, g1 = eng.lose_shards(dead)
        assert (g0, g1) == want
        assert eng.P == 4
        # the stale 2x4 factorization dropped with the dead host
        assert eng.host_topology is None
        info = eng.last_shard_loss
        assert info["dead_shards"] == dead
        assert info["survivor_rows"] > 0


# ------------------------------------------------- end-to-end failover


class TestHostLossVerify:
    def test_session_engine_host_loss_oracle_identical(self, tmp_path):
        report = run_shard_loss_verify(
            _mk_session_engine, _mk_session_oracle, _steps(),
            _host_loss_plan(), seed=7,
            ckpt_root=str(tmp_path / "c"), checkpoint_every=2)
        assert not report.diverged
        assert report.hosts_lost == 1
        assert report.shards_lost == 4  # the whole host's slice
        assert report.shard_restores == 1
        # bounded replay: HALF the key space (one of two hosts), only
        # since its units' checkpoint position — never the whole stream
        assert 0 < report.records_replayed <= report.events // 2
        assert report.shard_loss_recovery_ms > 0

    def test_forced_eviction_stays_on_the_path(self, tmp_path):
        holder = {}

        def mk():
            holder["eng"] = _mk_session_engine(slots=1024)
            return holder["eng"]

        report = run_shard_loss_verify(
            mk, _mk_session_oracle,
            _steps(num_keys=9000, per_step=2000),
            _host_loss_plan(), seed=7,
            ckpt_root=str(tmp_path / "c"), checkpoint_every=2)
        assert not report.diverged
        assert report.hosts_lost == 1
        assert holder["eng"].spill_counters()["rows_evicted"] > 0

    def test_seed_deterministic_signature(self, tmp_path):
        sigs = []
        for i in range(2):
            r = run_shard_loss_verify(
                _mk_session_engine, _mk_session_oracle, _steps(),
                _host_loss_plan(), seed=7,
                ckpt_root=str(tmp_path / f"c{i}"), checkpoint_every=2)
            sigs.append(r.signature())
        assert sigs[0] == sigs[1]
        assert sigs[0]["hosts_lost"] == 1

    def test_window_engine_host_loss(self, tmp_path):
        from flink_tpu.parallel.sharded_windower import MeshWindowEngine
        from flink_tpu.windowing.assigners import (
            TumblingEventTimeWindows,
        )
        from flink_tpu.windowing.windower import SliceSharedWindower

        def mk_engine():
            return MeshWindowEngine(
                TumblingEventTimeWindows.of(50), SumAggregate("v"),
                make_mesh(8), capacity_per_shard=1 << 14,
                host_topology=HostTopology(2, 4))

        def mk_oracle():
            return SliceSharedWindower(
                TumblingEventTimeWindows.of(50), SumAggregate("v"),
                capacity=1 << 15)

        report = run_shard_loss_verify(
            mk_engine, mk_oracle, _steps(), _host_loss_plan(),
            seed=11, ckpt_root=str(tmp_path / "c"),
            checkpoint_every=2)
        assert not report.diverged
        assert report.hosts_lost == 1
        assert report.shards_lost == 4

    def test_host_loss_before_first_checkpoint_replays_cold(
            self, tmp_path):
        report = run_shard_loss_verify(
            _mk_session_engine, _mk_session_oracle, _steps(),
            _host_loss_plan(nth=2), seed=7,
            ckpt_root=str(tmp_path / "c"), checkpoint_every=4)
        assert not report.diverged
        assert report.hosts_lost == 1


# ------------------------------------------------ two real processes


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _children(runs, timeout=300):
    """Start ``tests/pod_process.py`` once per argument list (JAX on
    the CPU, four virtual devices each) and wait for all of them."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                        f"{pod_process.LOCAL}")
    env["PYTHONPATH"] = (REPO + os.pathsep
                         + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    procs = [subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tests", "pod_process.py"),
         *args], cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for args in runs]
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            assert p.returncode == 0, out.decode()[-3000:]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()


def _pod(tmp, tag, kill_at=0):
    port = _free_port()
    ckpt = tmp / f"ckpt-{tag}"
    ckpt.mkdir()
    outs = [str(tmp / f"{tag}-{pid}.json")
            for pid in range(pod_process.HOSTS)]
    _children([["pod", "--out", outs[pid], "--pid", str(pid),
                "--port", str(port), "--ckpt", str(ckpt),
                "--kill-at", str(kill_at)]
               for pid in range(pod_process.HOSTS)])
    return [pod_process.load_committed(o) for o in outs]


def _merge(parts):
    merged = {}
    for part in parts:
        for k, v in part.items():
            assert merged.get(k, v) == v, f"conflicting cell {k}"
            merged[k] = v
    return merged


class TestTwoRealProcesses:
    """The protocol above across REAL process boundaries: two processes
    (``jax.distributed`` + gloo CPU collectives), each owning half the
    key-group space with its own metadata plane and checkpoint units,
    exchange records over the DCN axis on the device; then process 1
    is killed mid-stream. Each leg's measured rep runs fresh engines on
    a warm program cache."""

    @pytest.fixture(scope="class")
    def single(self, tmp_path_factory):
        out = str(tmp_path_factory.mktemp("single") / "single.json")
        _children([["single", "--out", out]])
        return pod_process.load_committed(out)

    @pytest.fixture(scope="class")
    def pod(self, tmp_path_factory):
        return _pod(tmp_path_factory.mktemp("pod"), "pod")

    @pytest.fixture(scope="class")
    def chaos(self, tmp_path_factory):
        kill_at = max(pod_process.n_batches() * 2 // 3,
                      pod_process.CKPT_EVERY + 1)
        assert kill_at < pod_process.n_batches() - 1
        return _pod(tmp_path_factory.mktemp("chaos"), "chaos", kill_at)

    def test_one_process_steady_state_compiles_nothing(self, single):
        oracle, meta = single
        assert len(oracle) > 0
        assert meta["compiles_measured"] == 0

    def test_two_processes_bit_identical_to_one(self, single, pod):
        assert _merge([part for part, _ in pod]) == single[0]

    def test_rows_cross_the_process_boundary_on_device(self, pod):
        assert sum(meta["cross_rows"] for _, meta in pod) > 0

    def test_two_process_steady_state_compiles_nothing(self, pod):
        assert [meta["compiles_measured"] for _, meta in pod] == [0, 0]

    def test_killed_process_range_restored_and_replay_bounded(
            self, single, chaos):
        """The survivor restores ONLY the dead host's ranges from its
        checkpoint units, replays at most the per-host share of the
        stream, and the union of both committed outputs is
        bit-identical to the one-process run."""
        (survivor, s_meta), (dead, _) = chaos
        assert _merge([dead, survivor]) == single[0]
        assert s_meta["restored_units"] >= 1
        assert 0 < s_meta["replayed"] <= pod_process.RECORDS // 2
