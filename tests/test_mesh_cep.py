"""Device-vectorized CEP (flink_tpu/cep/mesh_engine.py): the mesh NFA
engine over per-key computation-state columns on the state plane.

The contract under test, in order of importance:

1. BIT-IDENTITY: the device engine equals the host ``CepOperator``
   oracle row for row — same values, same emission order — across
   pattern shapes (multi-stage within-window sequences under both
   after-match skip strategies, consecutive ``times`` loops), including
   under forced paged eviction (always-alive pattern, keys >> budget)
   and a mid-stream live ``reshard()``.
2. ELIGIBILITY: ``compile_device_pattern`` admits exactly the
   bounded-partial class; every disqualifier raises
   ``UnsupportedCepPattern`` (the loud-fallback cue) instead of
   silently approximating, and the ``MeshCepOperator`` wrapper falls
   back to the host NFA while ticking the fallback counter.
3. CHECKPOINTS: snapshot -> restore round-trips mid-stream;
   ``snapshot_sharded`` units merge through ``merge_unit_snapshots``
   into a DIFFERENT shard count and replay identically.
4. SERVING: the matched-pattern store answers ``query_match_batch``
   and the replica-plane adapter returns the same rows, in process and
   through the multi-process frontend tier.
5. STEADY STATE: a fresh engine on the warm program cache compiles
   nothing.
"""

import tempfile

import numpy as np
import pytest

from flink_tpu.cep import (
    MeshCepEngine,
    UnsupportedCepPattern,
    compile_device_pattern,
    host_fallbacks,
)
from flink_tpu.cep.pattern import AfterMatchSkipStrategy as Skip
from flink_tpu.cep.pattern import Pattern
from flink_tpu.core.records import RecordBatch
from flink_tpu.parallel.mesh import make_mesh


def seq3(skip=Skip.SKIP_PAST_LAST_EVENT, within=50):
    p = (Pattern.begin("a", skip=skip)
         .where(lambda b: np.asarray(b["v"]) % 3 == 0)
         .next("b").where(lambda b: np.asarray(b["v"]) % 3 == 1)
         .next("c").where(lambda b: np.asarray(b["v"]) % 3 == 2))
    return p.within(within) if within else p


def churn_pattern():
    """Always-alive two-stage pattern: the virtual start keeps every
    seen key's column alive forever, so residency grows without bound
    and eviction is FORCED once keys exceed the slot budget."""
    return (Pattern.begin("a", skip=Skip.SKIP_PAST_LAST_EVENT)
            .next("b").where(lambda b: np.asarray(b["v"]) == 7))


def gen_steps(seed, n_steps=10, n_keys=40, batch=256, stride=25,
              spread=30):
    rng = np.random.default_rng(seed)
    ts = 0
    steps = []
    for _ in range(n_steps):
        keys = rng.integers(0, n_keys, size=batch).astype(np.int64)
        vals = rng.integers(0, 9, size=batch).astype(np.int64)
        tss = ts + np.sort(
            rng.integers(0, spread, size=batch)).astype(np.int64)
        ts += stride
        steps.append((keys, vals, tss, ts - 5))
    return steps


def mk_batch(keys, vals, tss):
    return RecordBatch.from_pydict(
        {"k": keys, "v": vals, "__key_id__": keys}, timestamps=tss)


def run(engine, steps, hook=None):
    out = []
    for i, (keys, vals, tss, wm) in enumerate(steps):
        out.extend(engine.process_batch(mk_batch(keys, vals, tss)))
        out.extend(engine.on_watermark(wm))
        if hook:
            engine = hook(engine, i) or engine
    return out, engine


def rows_of(batches):
    """Order-preserving flatten — a reordered emission diverges even
    when the value multiset matches."""
    rows = []
    for b in batches:
        for r, t in zip(b.to_rows(),
                        np.asarray(b.timestamps).tolist()):
            rows.append((t, tuple(sorted(r.items()))))
    return rows


def host_rows(pat, steps):
    out, _ = run(MeshCepEngine(pat, key_field="k", backend="host"),
                 steps)
    return rows_of(out)


def device(pat, shards=2, capacity=256, **kw):
    return MeshCepEngine(pat, key_field="k", mesh=make_mesh(shards),
                         capacity_per_shard=capacity,
                         max_parallelism=128, **kw)


class TestBitIdentity:
    @pytest.mark.parametrize("skip", [Skip.SKIP_PAST_LAST_EVENT,
                                      Skip.NO_SKIP])
    def test_seq3_within_matches_oracle(self, skip):
        pat = seq3(skip)
        steps = gen_steps(7, n_steps=12, n_keys=17, batch=64)
        want = host_rows(pat, steps)
        got, _ = run(device(pat, shards=4), steps)
        assert want, "vacuous: oracle emitted nothing"
        assert rows_of(got) == want

    def test_times_loop_matches_oracle(self):
        pat = (Pattern.begin("a", skip=Skip.NO_SKIP)
               .where(lambda b: np.asarray(b["v"]) < 5)
               .times(2, 3).consecutive()
               .next("end")
               .where(lambda b: np.asarray(b["v"]) >= 7)
               .within(60))
        steps = gen_steps(11, n_keys=40)
        want = host_rows(pat, steps)
        got, _ = run(device(pat), steps)
        assert want
        assert rows_of(got) == want

    def test_forced_eviction_matches_oracle(self):
        """Keys >> slot budget: the spill tier MUST churn (asserted —
        a vacuous pass would cover nothing) and output stays
        bit-identical through evict/reload."""
        pat = churn_pattern()
        steps = gen_steps(11, n_keys=5000, batch=256)
        want = host_rows(pat, steps)
        with tempfile.TemporaryDirectory() as td:
            eng = device(pat, spill_dir=td)
            got, eng = run(eng, steps)
            sc = eng.spill_counters()
        assert want
        assert rows_of(got) == want
        assert sc["rows_evicted"] > 0
        assert sc["rows_reloaded"] > 0

    @pytest.mark.parametrize("skip", [Skip.SKIP_PAST_LAST_EVENT,
                                      Skip.NO_SKIP])
    def test_seq3_dense_keys_on_eight_shards(self, skip):
        """The same 3-stage sequence with 256-record batches over 40
        dense keys on an 8-shard mesh: many keys complete per batch."""
        pat = seq3(skip)
        steps = gen_steps(7, n_steps=12, n_keys=40, batch=256)
        want = host_rows(pat, steps)
        got, _ = run(device(pat, shards=8), steps)
        assert want, "vacuous: oracle emitted nothing"
        assert rows_of(got) == want

    def test_forced_eviction_on_eight_shards(self):
        """The always-alive pattern over 20,000 keys on 8 shards of 256
        slots: evict AND reload both run, output bit-identical."""
        pat = churn_pattern()
        steps = gen_steps(11, n_steps=12, n_keys=20_000, batch=256)
        want = host_rows(pat, steps)
        with tempfile.TemporaryDirectory() as td:
            got, eng = run(device(pat, shards=8, spill_dir=td), steps)
            sc = eng.spill_counters()
        assert want
        assert rows_of(got) == want
        assert sc["rows_evicted"] > 0 and sc["rows_reloaded"] > 0

    def test_late_rows_dropped_like_oracle(self):
        pat = seq3()
        steps = gen_steps(3, n_keys=10, batch=32)
        # replay a batch far behind the fired watermark: both backends
        # must drop it (same late-drop policy) and tick the counter
        keys, vals, tss, _ = steps[0]
        h = MeshCepEngine(pat, key_field="k", backend="host")
        d = device(pat)
        hout, _ = run(h, steps)
        dout, _ = run(d, steps)
        for e in (h, d):
            assert e.process_batch(mk_batch(keys, vals, tss)) == []
            assert e.late_dropped >= len(keys)
        assert rows_of(hout) == rows_of(dout)


class TestEligibility:
    def test_eligible_class_compiles(self):
        lay = compile_device_pattern(seq3().validate())
        assert lay.n_states >= 1
        assert lay.has_within
        assert lay.key  # stable program-cache identity
        assert compile_device_pattern(churn_pattern().validate())

    @pytest.mark.parametrize("pat", [
        # greedy loop
        (Pattern.begin("a").where(lambda b: np.asarray(b["v"]) > 0)
         .one_or_more().greedy()
         .next("b").where(lambda b: np.asarray(b["v"]) < 0)),
        # unbounded loop
        (Pattern.begin("a").where(lambda b: np.asarray(b["v"]) > 0)
         .times_or_more(2).consecutive()
         .next("b").where(lambda b: np.asarray(b["v"]) < 0)),
        # non-consecutive times
        (Pattern.begin("a").where(lambda b: np.asarray(b["v"]) > 0)
         .times(2, 3)
         .next("b").where(lambda b: np.asarray(b["v"]) < 0)),
    ])
    def test_ineligible_raises(self, pat):
        with pytest.raises(UnsupportedCepPattern):
            compile_device_pattern(pat.validate())

    def test_operator_falls_back_loudly(self):
        from flink_tpu.cep import MeshCepOperator

        pat = (Pattern.begin("a")
               .where(lambda b: np.asarray(b["v"]) > 0)
               .one_or_more().greedy()
               .next("b").where(lambda b: np.asarray(b["v"]) < 0))
        op = MeshCepOperator(pat, key_field="k")
        before = host_fallbacks()

        class _Ctx:
            parallelism = 2
            mesh = None

        op.open(_Ctx())
        assert host_fallbacks() == before + 1
        assert op.engine.backend == "host"


class TestCheckpoints:
    def test_snapshot_restore_mid_stream(self):
        pat = seq3()
        steps = gen_steps(23, n_steps=12, n_keys=300, batch=256)
        want = host_rows(pat, steps)

        def hook(e, i):
            if i == 5:
                snap = e.snapshot()
                e2 = device(pat)
                e2.restore(snap)
                return e2

        got, _ = run(device(pat), steps, hook=hook)
        assert want
        assert rows_of(got) == want

    def test_sharded_merge_into_different_shard_count(self):
        pat = seq3()
        steps = gen_steps(23, n_steps=12, n_keys=300, batch=256)
        want = host_rows(pat, steps)

        def hook(e, i):
            if i == 6:
                units = e.snapshot_sharded()
                e2 = device(pat, shards=4)
                e2.restore(e2.merge_unit_snapshots(
                    list(units.values())))
                return e2

        got, _ = run(device(pat, shards=2), steps, hook=hook)
        assert rows_of(got) == want

    def test_live_reshard_mid_stream(self):
        pat = seq3()
        steps = gen_steps(23, n_steps=12, n_keys=300, batch=256)
        want = host_rows(pat, steps)

        def hook(e, i):
            if i == 4:
                info = e.reshard(2)
                assert info["shards"] == 2
                assert info["rows_moved"] > 0
            if i == 8:
                e.reshard(8)

        got, _ = run(device(pat, shards=4), steps, hook=hook)
        assert rows_of(got) == want


class TestMatchStore:
    def test_replica_lookup_equals_live_probe(self):
        pat = (Pattern.begin("a", skip=Skip.SKIP_PAST_LAST_EVENT)
               .where(lambda b: np.asarray(b["v"]) % 3 == 0)
               .next("b")
               .where(lambda b: np.asarray(b["v"]) % 3 == 1)
               .within(50))
        eng = device(pat, match_capacity=64)
        adapter = eng.arm_match_replica()
        steps = gen_steps(3, n_steps=10, n_keys=30, batch=128)
        _, eng = run(eng, steps)
        assert eng.matches_emitted > 0
        qkeys = np.arange(30, dtype=np.int64)
        live = eng.query_match_batch(qkeys)
        rep, _gen = adapter.lookup_batch(qkeys)
        assert sum(len(r) for r in live) > 0
        for i in range(30):
            assert live[i] == rep[i]
        # retained rids are unique (FIFO store, slot-deduped)
        rids = [r["rid"] for rows in live for r in rows]
        assert len(rids) == len(set(rids))

    def test_metrics_group_registers(self):
        from flink_tpu.metrics import MetricRegistry

        eng = device(seq3())
        reg = MetricRegistry()
        eng.register_metrics(reg.root_group("job"))
        steps = gen_steps(5, n_steps=4, n_keys=20, batch=64)
        run(eng, steps)
        assert eng.matches_emitted >= 0


class TestSteadyState:
    def test_fresh_engine_compiles_nothing_on_warm_cache(self):
        """After one device pass warmed the shared program cache, a
        FRESH engine replaying the same stream compiles ZERO XLA
        programs (the cep-advance / cep-prune program families)."""
        from flink_tpu.observe import RecompileSentinel

        pat = seq3()
        steps = gen_steps(7, n_steps=12, n_keys=40, batch=256)
        warm, _ = run(device(pat, shards=8), steps)
        assert rows_of(warm), "vacuous: nothing matched"
        with RecompileSentinel(max_compiles=0,
                               max_transfers=len(steps) * 64,
                               label="cep steady state"):
            got, _ = run(device(pat, shards=8), steps)
        assert rows_of(got) == rows_of(warm)


class TestMatchStoreFrontends:
    def test_frontend_pool_serves_match_rows_from_shm(self):
        """Matched-pattern lookups through the multi-process serving
        tier: the owner primes the shm hot cache through
        ``CepMatchServingAdapter``, two frontend processes probe it
        over shared memory, and every decoded row set equals the live
        ``query_match_batch`` probe. The second round must be served
        from shared memory (a packing regression would turn every
        probe into an owner crossing)."""
        import os
        import queue

        from flink_tpu.cep.mesh_engine import CepMatchServingAdapter
        from flink_tpu.native import hotcache_available
        from flink_tpu.tenancy.frontend import FrontendPool
        from flink_tpu.tenancy.serving import ServingPlane

        if not hotcache_available():
            pytest.skip("native hotcache unavailable")
        pat = seq3()
        steps = gen_steps(7, n_steps=12, n_keys=40, batch=256)
        with tempfile.TemporaryDirectory() as td:
            plane = ServingPlane(shm_dir=os.path.join(td, "hc"))
            try:
                engine = device(pat, shards=8)
                adapter = engine.arm_match_replica(serving=True)
                assert isinstance(adapter, CepMatchServingAdapter)
                plane.bind_job("cep", queue.Queue())
                plane.bind_replica("cep", "matches", adapter)
                run(engine, steps)
                qkeys = np.arange(40, dtype=np.int64)
                live = engine.query_match_batch(qkeys)
                assert sum(len(r) for r in live) > 0
                with FrontendPool(plane, n_frontends=2) as pool:
                    pool.wait_ready()
                    # round 1 fills the shm table through the miss
                    # path; round 2 is served out of process
                    pool.lookup_batch("cep", "matches", qkeys.tolist())
                    got = pool.lookup_batch("cep", "matches",
                                            qkeys.tolist())
                    stats = pool.fe_stats()
                for i in range(40):
                    assert CepMatchServingAdapter.match_rows(got[i]) \
                        == live[i], i
                assert sum(r.get("probes_hit", r.get("hits", 0))
                           for r in stats) > 0
            finally:
                plane.shutdown_workers()
