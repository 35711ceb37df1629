"""flint (tools/flint) — the TPU-tracing static analyzer — and the
recompile sentinel (flink_tpu/observe).

Covers: a failing fixture per rule (TRC01/TRC02/JIT01/REG01/REG02/
REG04/NAT01 and the r24 concurrency rules LCK01/LCK02/LCK03/SHM01), the
suppression protocol (reason mandatory), the clean-tree invariant
(flint exits 0 over flink_tpu/ at HEAD — the same gate tools/tier1.sh
runs), the --rule CLI filter + per-rule timings in the JSON report,
the sentinel's compile/transfer accounting, and the
slow-lane bookkeeping of the known-flaky unaligned-checkpoint timing
test (deflake follow-up)."""

import json
from pathlib import Path

import pytest

from tools.flint.core import Project, discover, run_checks

REPO_ROOT = Path(__file__).resolve().parents[1]


def run_fixture(tmp_path, files, select):
    """Write a throwaway mini-package and run the selected rules."""
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text, encoding="utf-8")
    project = Project(discover(["flink_tpu/"], tmp_path), tmp_path)
    return run_checks(project, select=select)


# ------------------------------------------------------------------- TRC01


class TestTRC01HostSync:
    FILES = {
        "flink_tpu/__init__.py": "",
        "flink_tpu/eng.py": (
            "import numpy as np\n"
            "\n"
            "class MeshWindowEngine:\n"
            "    def process_batch(self, batch):\n"
            "        out = self._gather_step(batch)\n"
            "        return [np.asarray(g) for g in out]\n"
        ),
    }

    def test_per_array_read_on_step_result_trips(self, tmp_path):
        active, _ = run_fixture(tmp_path, self.FILES, ["TRC01"])
        assert [v.rule for v in active] == ["TRC01"]
        assert "np.asarray" in active[0].message
        assert active[0].path == "flink_tpu/eng.py"

    def test_reachability_is_required(self, tmp_path):
        # same sync, but in a class/method no hot root reaches: clean
        files = dict(self.FILES)
        files["flink_tpu/eng.py"] = files["flink_tpu/eng.py"].replace(
            "MeshWindowEngine", "SomeColdHelper")
        active, _ = run_fixture(tmp_path, files, ["TRC01"])
        assert active == []

    def test_block_until_ready_trips_transitively(self, tmp_path):
        files = {
            "flink_tpu/__init__.py": "",
            "flink_tpu/eng.py": (
                "class MeshSessionEngine:\n"
                "    def on_watermark(self, wm):\n"
                "        self._drain()\n"
                "    def _drain(self):\n"
                "        self.fence.block_until_ready()\n"
            ),
        }
        active, _ = run_fixture(tmp_path, files, ["TRC01"])
        assert len(active) == 1
        assert "block_until_ready" in active[0].message

    def test_scalar_cast_of_device_value_trips(self, tmp_path):
        files = {
            "flink_tpu/__init__.py": "",
            "flink_tpu/eng.py": (
                "class SlotTable:\n"
                "    def fire(self, sm):\n"
                "        merged = self._fire_jit(self.accs, sm)\n"
                "        return int(merged[0])\n"
            ),
        }
        active, _ = run_fixture(tmp_path, files, ["TRC01"])
        assert len(active) == 1
        assert "int() on a device value" in active[0].message


# ------------------------------------------------------------------- TRC02


class TestTRC02TracerControlFlow:
    def test_if_on_jit_argument_trips(self, tmp_path):
        files = {
            "flink_tpu/__init__.py": "",
            "flink_tpu/k.py": (
                "import jax\n"
                "\n"
                "@jax.jit\n"
                "def step(x):\n"
                "    if x > 0:\n"
                "        return x\n"
                "    return -x\n"
            ),
        }
        active, _ = run_fixture(tmp_path, files, ["TRC02"])
        assert [v.rule for v in active] == ["TRC02"]
        assert "data-dependent" in active[0].message

    def test_shape_checks_are_trace_time_static(self, tmp_path):
        files = {
            "flink_tpu/__init__.py": "",
            "flink_tpu/k.py": (
                "import jax\n"
                "\n"
                "@jax.jit\n"
                "def step(x):\n"
                "    if x.shape[0] > 4:\n"
                "        return x[:4]\n"
                "    return x\n"
            ),
        }
        active, _ = run_fixture(tmp_path, files, ["TRC02"])
        assert active == []

    def test_while_on_derived_value_in_wrapped_fn(self, tmp_path):
        files = {
            "flink_tpu/__init__.py": "",
            "flink_tpu/k.py": (
                "import jax\n"
                "\n"
                "def body(x):\n"
                "    y = x * 2\n"
                "    while y < 10:\n"
                "        y = y + 1\n"
                "    return y\n"
                "\n"
                "stepped = jax.jit(body)\n"
            ),
        }
        active, _ = run_fixture(tmp_path, files, ["TRC02"])
        assert len(active) == 1
        assert "while" in active[0].message


# ------------------------------------------------------------------- JIT01


class TestJIT01UnstableIdentity:
    def test_jit_lambda_per_call_trips(self, tmp_path):
        files = {
            "flink_tpu/__init__.py": "",
            "flink_tpu/k.py": (
                "import jax\n"
                "\n"
                "def step(v):\n"
                "    return jax.jit(lambda a: a + 1)(v)\n"
            ),
        }
        active, _ = run_fixture(tmp_path, files, ["JIT01"])
        assert [v.rule for v in active] == ["JIT01"]
        assert "fresh jit identity" in active[0].message

    def test_jit_local_def_in_loop_trips(self, tmp_path):
        files = {
            "flink_tpu/__init__.py": "",
            "flink_tpu/k.py": (
                "import jax\n"
                "\n"
                "def build(xs):\n"
                "    out = []\n"
                "    for x in xs:\n"
                "        def k(a):\n"
                "            return a * 2\n"
                "        out.append(jax.jit(k)(x))\n"
                "    return out\n"
            ),
        }
        active, _ = run_fixture(tmp_path, files, ["JIT01"])
        assert len(active) == 1
        assert "loop" in active[0].message

    def test_module_level_and_cached_builders_pass(self, tmp_path):
        files = {
            "flink_tpu/__init__.py": "",
            "flink_tpu/k.py": (
                "import jax\n"
                "\n"
                "_FENCE = jax.jit(lambda a: a[:1])\n"
                "_JIT_CACHE = {}\n"
                "\n"
                "def make_fence(acc):\n"
                "    fn = _JIT_CACHE.get('fence')\n"
                "    if fn is None:\n"
                "        fn = jax.jit(lambda a: a[:1, :1])\n"
                "        _JIT_CACHE['fence'] = fn\n"
                "    return fn(acc)\n"
            ),
        }
        active, _ = run_fixture(tmp_path, files, ["JIT01"])
        assert active == []


# ------------------------------------------------------------------- REG01


class TestREG01FaultPointRegistry:
    FILES = {
        "flink_tpu/__init__.py": "",
        "flink_tpu/chaos/__init__.py": (
            'KNOWN_FAULT_POINTS = ("good.point", "stale.point")\n'
        ),
        "flink_tpu/mod.py": (
            "from flink_tpu.chaos import injection as chaos\n"
            "\n"
            "def f():\n"
            '    chaos.fault_point("good.point")\n'
            '    chaos.fault_point("typo.poimt")\n'
        ),
        "tests/__init__.py": "",
        "tests/test_x.py": (
            "from flink_tpu.chaos.injection import FaultRule\n"
            "\n"
            'R1 = FaultRule(pattern="good.*", nth=1)\n'
            'R2 = FaultRule(pattern="zzz.never", nth=1)\n'
        ),
    }

    def test_typos_stales_and_dead_patterns_trip(self, tmp_path):
        active, _ = run_fixture(tmp_path, self.FILES, ["REG01"])
        msgs = "\n".join(v.message for v in active)
        assert "'typo.poimt' is not in" in msgs
        assert "'stale.point' has no" in msgs
        assert "'zzz.never' matches no known fault point" in msgs
        assert len(active) == 3

    def test_clean_registry_passes(self, tmp_path):
        files = dict(self.FILES)
        files["flink_tpu/chaos/__init__.py"] = \
            'KNOWN_FAULT_POINTS = ("good.point", "typo.poimt")\n'
        files["tests/test_x.py"] = (
            "from flink_tpu.chaos.injection import FaultRule\n"
            'R1 = FaultRule(pattern="good.*", nth=1)\n'
        )
        active, _ = run_fixture(tmp_path, files, ["REG01"])
        assert active == []


# ------------------------------------------------------------------- REG02


class TestREG02MetricCounterRegistry:
    FILES = {
        "flink_tpu/__init__.py": "",
        "flink_tpu/state/__init__.py": "",
        "flink_tpu/state/paged_spill.py": (
            'COUNTER_NAMES = ("rows_ok",)\n'
        ),
        "flink_tpu/metrics/__init__.py": (
            'KNOWN_METRIC_GROUPS = ("good", "unproduced")\n'
        ),
        "flink_tpu/prod.py": (
            "def bump(counters, g):\n"
            '    counters["rows_ok"] += 1\n'
            '    counters["rows_typo"] += 1\n'
            '    g.add_group("good")\n'
            '    g.add_group("bogus")\n'
        ),
    }

    def test_counter_and_group_drift_trips(self, tmp_path):
        active, _ = run_fixture(tmp_path, self.FILES, ["REG02"])
        msgs = "\n".join(v.message for v in active)
        assert "'rows_typo' is not in" in msgs
        assert "'bogus' is not in" in msgs
        assert "'unproduced' has no add_group producer" in msgs
        assert len(active) == 3


# ------------------------------------------------------------------- REG04


class TestREG04ProgramFamilyRegistry:
    FILES = {
        "flink_tpu/__init__.py": "",
        "flink_tpu/stateplane/__init__.py": "",
        "flink_tpu/stateplane/families.py": (
            'KNOWN_PROGRAM_FAMILIES = ("gather", "stale-family")\n'
        ),
        "flink_tpu/mod.py": (
            "from flink_tpu.tenancy.program_cache import PROGRAM_CACHE\n"
            "\n"
            "def build(key, builder):\n"
            '    PROGRAM_CACHE.get_or_build("gather", key, builder)\n'
            '    PROGRAM_CACHE.get_or_build("gahter", key, builder)\n'
        ),
    }

    def test_typo_kind_and_stale_entry_trip(self, tmp_path):
        active, _ = run_fixture(tmp_path, self.FILES, ["REG04"])
        msgs = "\n".join(v.message for v in active)
        assert "'gahter' is not in" in msgs
        assert "'stale-family' has no" in msgs
        assert len(active) == 2
        # the typo points at the producing call site, not the registry
        typo = next(v for v in active if "gahter" in v.message)
        assert typo.path == "flink_tpu/mod.py"

    def test_clean_inventory_passes(self, tmp_path):
        files = dict(self.FILES)
        files["flink_tpu/stateplane/families.py"] = \
            'KNOWN_PROGRAM_FAMILIES = ("gather", "gahter")\n'
        active, _ = run_fixture(tmp_path, files, ["REG04"])
        assert active == []

    def test_missing_registry_tuple_is_a_violation(self, tmp_path):
        files = dict(self.FILES)
        files["flink_tpu/stateplane/families.py"] = "def helper():\n    pass\n"
        active, _ = run_fixture(tmp_path, files, ["REG04"])
        assert len(active) == 1
        assert "KNOWN_PROGRAM_FAMILIES" in active[0].message


# ------------------------------------------------------------------- NAT01


class TestNAT01NativeCtypesSignatures:
    FILES = {
        "flink_tpu/__init__.py": "",
        "flink_tpu/native/__init__.py": (
            'NATIVE_SYMBOL_PREFIXES = ("sm_", "sx_")\n'
            "\n"
            "def load_slotmap():\n"
            "    lib = _load()\n"
            "    lib.sm_good.restype = None\n"
            "    lib.sm_good.argtypes = []\n"
            "    lib.sm_partial.argtypes = []\n"  # restype missing
            "    return lib\n"
        ),
        "flink_tpu/user.py": (
            "def run(lib):\n"
            "    lib.sm_good()\n"
            "    lib.sm_partial()\n"
            "    lib.sx_undeclared(3)\n"  # no declaration at all
        ),
    }

    def test_missing_and_partial_signatures_trip(self, tmp_path):
        active, _ = run_fixture(tmp_path, self.FILES, ["NAT01"])
        msgs = "\n".join(v.message for v in active)
        assert "'sx_undeclared' is called without argtypes and restype" \
            in msgs
        assert "'sm_partial' is called without restype" in msgs
        assert "'sm_partial' declares ['argtypes'] but not restype" \
            in msgs
        assert "sm_good" not in msgs
        assert len(active) == 3

    def test_clean_declarations_pass(self, tmp_path):
        files = dict(self.FILES)
        files["flink_tpu/native/__init__.py"] = (
            'NATIVE_SYMBOL_PREFIXES = ("sm_", "sx_")\n'
            "def load_all():\n"
            "    lib = _load()\n"
            "    for s in ('sm_good', 'sm_partial', 'sx_undeclared'):\n"
            "        pass\n"
            "    lib.sm_good.restype = None\n"
            "    lib.sm_good.argtypes = []\n"
            "    lib.sm_partial.restype = None\n"
            "    lib.sm_partial.argtypes = []\n"
            "    lib.sx_undeclared.restype = None\n"
            "    lib.sx_undeclared.argtypes = []\n"
            "    return lib\n"
        )
        active, _ = run_fixture(tmp_path, files, ["NAT01"])
        assert active == []

    def test_missing_prefix_registry_is_a_violation(self, tmp_path):
        files = dict(self.FILES)
        files["flink_tpu/native/__init__.py"] = "def load():\n    pass\n"
        active, _ = run_fixture(tmp_path, files, ["NAT01"])
        assert len(active) == 1
        assert "NATIVE_SYMBOL_PREFIXES" in active[0].message

    def test_head_tree_is_clean_for_nat01(self, tmp_path):
        # the real package: every native symbol called anywhere has a
        # full ctypes signature in its loader (the codec_free restype
        # this rule caught on introduction stays fixed)
        project = Project(
            discover(["flink_tpu/"], REPO_ROOT), REPO_ROOT)
        active, _ = run_checks(project, select=["NAT01"])
        assert active == []


# ------------------------------------------------------------------- LCK01


class TestLCK01GuardedFieldDiscipline:
    FILES = {
        "flink_tpu/__init__.py": "",
        "flink_tpu/ledger.py": (
            "import threading\n"
            "\n"
            "class Ledger:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.count = 0\n"
            "\n"
            "    def bump(self):\n"
            "        with self._lock:\n"
            "            self.count += 1\n"
            "\n"
            "    def bump_twice(self):\n"
            "        with self._lock:\n"
            "            self.count += 2\n"
            "\n"
            "    def peek(self):\n"
            "        return self.count\n"
        ),
    }

    def test_unguarded_read_of_majority_guarded_field_trips(
            self, tmp_path):
        active, _ = run_fixture(tmp_path, self.FILES, ["LCK01"])
        assert [v.rule for v in active] == ["LCK01"]
        assert "'self.count' is guarded by 'self._lock'" \
            in active[0].message
        assert "peek" in active[0].message

    def test_guarded_everywhere_is_clean(self, tmp_path):
        files = dict(self.FILES)
        files["flink_tpu/ledger.py"] = files[
            "flink_tpu/ledger.py"].replace(
            "    def peek(self):\n"
            "        return self.count\n",
            "    def peek(self):\n"
            "        with self._lock:\n"
            "            return self.count\n")
        active, _ = run_fixture(tmp_path, files, ["LCK01"])
        assert active == []

    def test_majority_tie_infers_no_guard(self, tmp_path):
        # 1 of 2 write sites hold the lock: no strict majority, no
        # inference, no violations — the rule must not guess
        files = {
            "flink_tpu/__init__.py": "",
            "flink_tpu/ledger.py": (
                "import threading\n"
                "\n"
                "class Half:\n"
                "    def __init__(self):\n"
                "        self._lock = threading.Lock()\n"
                "        self.n = 0\n"
                "\n"
                "    def locked_write(self):\n"
                "        with self._lock:\n"
                "            self.n = 1\n"
                "\n"
                "    def bare_write(self):\n"
                "        self.n = 2\n"
            ),
        }
        active, _ = run_fixture(tmp_path, files, ["LCK01"])
        assert active == []

    def test_module_scope_globals_are_checked(self, tmp_path):
        files = {
            "flink_tpu/__init__.py": "",
            "flink_tpu/reg.py": (
                "import threading\n"
                "\n"
                "_lock = threading.Lock()\n"
                "_registry = {}\n"
                "\n"
                "def put(k, v):\n"
                "    with _lock:\n"
                "        _registry[k] = v\n"
                "\n"
                "def drop(k):\n"
                "    with _lock:\n"
                "        _registry.pop(k, None)\n"
                "\n"
                "def peek():\n"
                "    return sorted(_registry)\n"
            ),
        }
        active, _ = run_fixture(tmp_path, files, ["LCK01"])
        assert len(active) == 1
        assert "_registry" in active[0].message
        assert "peek" in active[0].message


# ------------------------------------------------------------------- LCK02


class TestLCK02LockOrderConsistency:
    FILES = {
        "flink_tpu/__init__.py": "",
        "flink_tpu/pipe.py": (
            "import threading\n"
            "\n"
            "class Pipeline:\n"
            "    def __init__(self):\n"
            "        self.a = threading.Lock()\n"
            "        self.b = threading.Lock()\n"
            "\n"
            "    def forward(self):\n"
            "        with self.a:\n"
            "            with self.b:\n"
            "                pass\n"
            "\n"
            "    def backward(self):\n"
            "        with self.b:\n"
            "            with self.a:\n"
            "                pass\n"
        ),
    }

    def test_ab_ba_cycle_trips_with_both_witnesses(self, tmp_path):
        active, _ = run_fixture(tmp_path, self.FILES, ["LCK02"])
        assert len(active) == 1
        msg = active[0].message
        assert "potential deadlock" in msg
        assert "Pipeline.a" in msg and "Pipeline.b" in msg
        # both legs of the cycle carry a witness site
        assert msg.count("pipe.py") >= 2

    def test_consistent_order_is_clean(self, tmp_path):
        files = dict(self.FILES)
        files["flink_tpu/pipe.py"] = files["flink_tpu/pipe.py"].replace(
            "    def backward(self):\n"
            "        with self.b:\n"
            "            with self.a:\n",
            "    def backward(self):\n"
            "        with self.a:\n"
            "            with self.b:\n")
        active, _ = run_fixture(tmp_path, files, ["LCK02"])
        assert active == []

    def test_cycle_through_a_call_edge_trips(self, tmp_path):
        # the b->a leg hides behind a method call under the held lock
        files = {
            "flink_tpu/__init__.py": "",
            "flink_tpu/pipe.py": (
                "import threading\n"
                "\n"
                "class Pipeline:\n"
                "    def __init__(self):\n"
                "        self.a = threading.Lock()\n"
                "        self.b = threading.Lock()\n"
                "\n"
                "    def forward(self):\n"
                "        with self.a:\n"
                "            with self.b:\n"
                "                pass\n"
                "\n"
                "    def drain(self):\n"
                "        with self.b:\n"
                "            self._grab_a()\n"
                "\n"
                "    def _grab_a(self):\n"
                "        with self.a:\n"
                "            pass\n"
            ),
        }
        active, _ = run_fixture(tmp_path, files, ["LCK02"])
        assert len(active) == 1
        assert "potential deadlock" in active[0].message


# ------------------------------------------------------------------- LCK03


class TestLCK03CheckThenAct:
    FILES = {
        "flink_tpu/__init__.py": "",
        "flink_tpu/reg.py": (
            "import threading\n"
            "\n"
            "class Registry:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._items = {}\n"
            "\n"
            "    def put_if_absent(self, k, v):\n"
            "        with self._lock:\n"
            "            missing = k not in self._items\n"
            "        if missing:\n"
            "            with self._lock:\n"
            "                self._items[k] = v\n"
        ),
    }

    def test_check_then_act_across_release_trips(self, tmp_path):
        active, _ = run_fixture(tmp_path, self.FILES, ["LCK03"])
        assert [v.rule for v in active] == ["LCK03"]
        assert "_items" in active[0].message
        assert "release" in active[0].message

    def test_recheck_under_second_hold_is_exempt(self, tmp_path):
        # the compare-and-restore / drain-loop idiom: the second region
        # RE-READS the field under its own hold before acting — clean
        files = dict(self.FILES)
        files["flink_tpu/reg.py"] = files["flink_tpu/reg.py"].replace(
            "        if missing:\n"
            "            with self._lock:\n"
            "                self._items[k] = v\n",
            "        if missing:\n"
            "            with self._lock:\n"
            "                if k not in self._items:\n"
            "                    self._items[k] = v\n")
        active, _ = run_fixture(tmp_path, files, ["LCK03"])
        assert active == []

    def test_single_hold_is_clean(self, tmp_path):
        files = dict(self.FILES)
        files["flink_tpu/reg.py"] = (
            "import threading\n"
            "\n"
            "class Registry:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._items = {}\n"
            "\n"
            "    def put_if_absent(self, k, v):\n"
            "        with self._lock:\n"
            "            if k not in self._items:\n"
            "                self._items[k] = v\n"
        )
        active, _ = run_fixture(tmp_path, files, ["LCK03"])
        assert active == []


# ------------------------------------------------------------------- SHM01


class TestSHM01AttachedHandleWriteDiscipline:
    NATIVE = (
        'NATIVE_SYMBOL_PREFIXES = ("hc_",)\n'
        'HOTCACHE_WRITER_SYMBOLS = ("hc_put_batch", "hc_drop")\n'
    )
    FILES = {
        "flink_tpu/__init__.py": "",
        "flink_tpu/native/__init__.py": NATIVE,
        "flink_tpu/fe.py": (
            "class FrontendClient:\n"
            "    def attach(self, lib, path):\n"
            "        self.ptr = lib.hc_attach(path)\n"
            "\n"
            "    def corrupt(self, lib):\n"
            "        lib.hc_put_batch(self.ptr)\n"
        ),
    }

    def test_writer_symbol_in_attach_scope_trips(self, tmp_path):
        active, _ = run_fixture(tmp_path, self.FILES, ["SHM01"])
        assert [v.rule for v in active] == ["SHM01"]
        assert "hc_put_batch" in active[0].message
        assert active[0].path == "flink_tpu/fe.py"

    def test_writer_in_owner_scope_is_clean(self, tmp_path):
        files = dict(self.FILES)
        files["flink_tpu/fe.py"] = (
            "class OwnerCache:\n"
            "    def prime(self, lib, ptr):\n"
            "        lib.hc_put_batch(ptr)\n"
        )
        active, _ = run_fixture(tmp_path, files, ["SHM01"])
        assert active == []

    def test_missing_writer_registry_is_a_violation(self, tmp_path):
        files = dict(self.FILES)
        files["flink_tpu/native/__init__.py"] = \
            'NATIVE_SYMBOL_PREFIXES = ("hc_",)\n'
        active, _ = run_fixture(tmp_path, files, ["SHM01"])
        assert any("HOTCACHE_WRITER_SYMBOLS" in v.message
                   for v in active)


# ------------------------------------------------------- conc suppressions


class TestConcSuppressions:
    def test_reasoned_lck01_suppression_silences(self, tmp_path):
        files = dict(TestLCK01GuardedFieldDiscipline.FILES)
        files["flink_tpu/ledger.py"] = files[
            "flink_tpu/ledger.py"].replace(
            "    def peek(self):\n"
            "        return self.count\n",
            "    def peek(self):\n"
            "        # flint: disable=LCK01 -- fixture: approximate "
            "gauge read\n"
            "        return self.count\n")
        active, suppressed = run_fixture(tmp_path, files,
                                         ["LCK01", "SUP01"])
        assert active == []
        assert len(suppressed) == 1
        assert suppressed[0].reason == "fixture: approximate gauge read"

    def test_bare_lck03_suppression_still_fails_sup01(self, tmp_path):
        files = dict(TestLCK03CheckThenAct.FILES)
        files["flink_tpu/reg.py"] = files["flink_tpu/reg.py"].replace(
            "        if missing:\n"
            "            with self._lock:\n",
            "        if missing:\n"
            "            # flint: disable=LCK03\n"
            "            with self._lock:\n")
        active, suppressed = run_fixture(tmp_path, files,
                                         ["LCK03", "SUP01"])
        assert [v.rule for v in active] == ["SUP01"]
        assert "without a reason" in active[0].message
        assert len(suppressed) == 1


# ------------------------------------------------------------- suppressions


class TestSuppressions:
    BAD = (
        "import numpy as np\n"
        "\n"
        "class MeshWindowEngine:\n"
        "    def process_batch(self, batch):\n"
        "        out = self._gather_step(batch)\n"
        "{directive}"
        "        return [np.asarray(g) for g in out]\n"
    )

    def test_reasoned_suppression_silences(self, tmp_path):
        files = {
            "flink_tpu/__init__.py": "",
            "flink_tpu/eng.py": self.BAD.format(directive=(
                "        # flint: disable=TRC01 -- fixture: deliberate\n"
            )),
        }
        active, suppressed = run_fixture(tmp_path, files,
                                         ["TRC01", "SUP01"])
        assert active == []
        assert len(suppressed) == 1
        assert suppressed[0].reason == "fixture: deliberate"

    def test_suppression_without_reason_is_a_violation(self, tmp_path):
        files = {
            "flink_tpu/__init__.py": "",
            "flink_tpu/eng.py": self.BAD.format(directive=(
                "        # flint: disable=TRC01\n"
            )),
        }
        active, suppressed = run_fixture(tmp_path, files,
                                         ["TRC01", "SUP01"])
        assert [v.rule for v in active] == ["SUP01"]
        assert "without a reason" in active[0].message
        assert len(suppressed) == 1  # suppressed, but the gate still fails

    def test_unknown_rule_in_directive_is_flagged(self, tmp_path):
        files = {
            "flink_tpu/__init__.py": "",
            "flink_tpu/eng.py": (
                "x = 1  # flint: disable=NOPE99 -- misguided\n"
            ),
        }
        active, _ = run_fixture(tmp_path, files, ["SUP01"])
        assert [v.rule for v in active] == ["SUP01"]
        assert "unknown rule" in active[0].message


# --------------------------------------------------------------- clean tree


class TestCleanTree:
    def test_flint_exits_zero_on_head(self, tmp_path):
        """The acceptance invariant tier-1 enforces: the real package is
        flint-clean and every suppression carries a reason."""
        from tools.flint.cli import main

        report = tmp_path / "flint_report.json"
        rc = main([str(REPO_ROOT / "flink_tpu"), "--json", str(report)])
        data = json.loads(report.read_text())
        assert rc == 0, data["violations"]
        assert data["violations"] == []
        assert {"TRC01", "TRC02", "JIT01", "REG01", "REG02", "REG04",
                "LCK01", "LCK02", "LCK03", "SHM01"} <= set(data["rules"])
        for s in data["suppressed"]:
            assert s["reason"], f"reasonless suppression: {s}"

    def test_rule_filter_and_per_rule_timings(self, tmp_path):
        """--rule runs only the named rules and the JSON report carries
        their wall time (the tier-1 guard on conc-rule cost bloat)."""
        from tools.flint.cli import main

        pkg = tmp_path / "flink_tpu"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "eng.py").write_text(
            "import numpy as np\n"
            "import threading\n"
            "\n"
            "class MeshWindowEngine:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.n = 0\n"
            "    def process_batch(self, batch):\n"
            "        out = self._gather_step(batch)\n"
            "        return [np.asarray(g) for g in out]\n"
            "    def bump(self):\n"
            "        with self._lock:\n"
            "            self.n += 1\n"
            "    def bump2(self):\n"
            "        with self._lock:\n"
            "            self.n += 1\n"
            "    def peek(self):\n"
            "        return self.n\n", encoding="utf-8")
        report = tmp_path / "r.json"
        # only LCK01 selected: the TRC01 host sync must NOT surface
        rc = main([str(pkg), "--rule", "LCK01", "--json", str(report)])
        assert rc == 1
        data = json.loads(report.read_text())
        assert {v["rule"] for v in data["violations"]} == {"LCK01"}
        assert set(data["rule_times_s"]) == {"LCK01"}
        assert all(t >= 0 for t in data["rule_times_s"].values())
        # repeatable + combines: both rules now surface
        rc = main([str(pkg), "--rule", "LCK01", "--rule", "TRC01",
                   "--json", str(report)])
        assert rc == 1
        data = json.loads(report.read_text())
        assert {v["rule"] for v in data["violations"]} == \
            {"LCK01", "TRC01"}
        assert set(data["rule_times_s"]) == {"LCK01", "TRC01"}

    def test_unknown_rule_flag_is_a_usage_error(self, capsys):
        from tools.flint.cli import main

        rc = main([str(REPO_ROOT / "flink_tpu"), "--rule", "NOPE99"])
        assert rc == 2
        assert "unknown rule" in capsys.readouterr().err

    def test_nonexistent_target_is_a_usage_error(self, capsys):
        """A typo'd path must exit 2 with a diagnostic, not traceback."""
        from tools.flint.cli import main

        rc = main([str(REPO_ROOT / "flink_tpu" / "nonexistent.py")])
        assert rc == 2
        assert "no such file" in capsys.readouterr().err

    def test_known_fault_points_matches_runtime_registry(self):
        """flint parses the tuple statically; the import path must agree."""
        import ast

        from flink_tpu.chaos import KNOWN_FAULT_POINTS

        src = (REPO_ROOT / "flink_tpu/chaos/__init__.py").read_text()
        tree = ast.parse(src)
        for node in tree.body:
            if isinstance(node, ast.Assign) and any(
                    getattr(t, "id", None) == "KNOWN_FAULT_POINTS"
                    for t in node.targets):
                parsed = tuple(e.value for e in node.value.elts)
                assert parsed == KNOWN_FAULT_POINTS
                return
        pytest.fail("KNOWN_FAULT_POINTS literal not found")


# ----------------------------------------------------------- the sentinel


class TestRecompileSentinel:
    def test_counts_fresh_compile_and_passes_cache_hits(self):
        import jax
        import jax.numpy as jnp

        from flink_tpu.observe import RecompileSentinel

        with RecompileSentinel(max_compiles=None) as warm:
            f = jax.jit(lambda x: x * 3 + 1)
            f(jnp.ones(17))
        assert warm.compiles >= 1  # fresh identity + shape => compiled
        with RecompileSentinel(max_compiles=0, label="steady") as s:
            f(jnp.ones(17))  # cache hit: same identity, same shape
        assert s.compiles == 0

    def test_raises_on_budget_violation(self):
        import jax
        import jax.numpy as jnp

        from flink_tpu.observe import (
            RecompileSentinel,
            SteadyStateViolation,
        )

        with pytest.raises(SteadyStateViolation, match="jit identity"):
            with RecompileSentinel(max_compiles=0, label="fixture"):
                jax.jit(lambda x: x - 7)(jnp.ones(9))

    def test_transfer_budget(self):
        import jax
        import jax.numpy as jnp

        from flink_tpu.observe import (
            RecompileSentinel,
            SteadyStateViolation,
        )

        x = jnp.arange(8)
        with RecompileSentinel(max_compiles=None) as s:
            jax.device_get(x)
        assert s.transfers >= 1
        with pytest.raises(SteadyStateViolation, match="transfer"):
            with RecompileSentinel(max_compiles=None, max_transfers=0):
                jax.device_get(x)

    def test_never_masks_region_exception(self):
        from flink_tpu.observe import RecompileSentinel

        with pytest.raises(ValueError, match="inner"):
            with RecompileSentinel(max_compiles=0):
                raise ValueError("inner")


# --------------------------------------------- deflake bookkeeping (satellite)


class TestSlowLaneBookkeeping:
    def test_unaligned_timing_test_stays_in_slow_lane(self):
        """The known-flaky wall-clock assertion must keep its slow
        marker, keep the justification comment explaining WHY, and the
        tier-1 gate must keep excluding the slow lane."""
        src = (REPO_ROOT / "tests/test_unaligned_checkpoint.py") \
            .read_text()
        i_mark = src.index("@pytest.mark.slow")
        i_test = src.index("def test_barrier_overtakes_backlog")
        assert i_mark < i_test, "slow marker must precede the timing test"
        justification = src[:i_mark]
        assert "WALL-CLOCK" in justification and "flaked" in justification, \
            "the slow marker lost its justification comment"
        tier1 = (REPO_ROOT / "tools/tier1.sh").read_text()
        assert "not slow" in tier1, "tier-1 no longer excludes slow tests"

    def test_slow_marker_is_registered(self):
        src = (REPO_ROOT / "tests/conftest.py").read_text()
        assert '"markers"' in src and "slow:" in src

    def test_gate_runs_what_exists(self):
        """Every script and module tools/tier1.sh runs is a file of the
        tree, and its pytest line is the driver's: the slow lane out,
        6 xdist workers, one file per worker at a time."""
        import re

        tier1 = (REPO_ROOT / "tools/tier1.sh").read_text()
        code = "\n".join(ln for ln in tier1.splitlines()
                         if not ln.lstrip().startswith("#"))
        scripts = re.findall(r"python3? +([\w./-]+\.py)\b", code)
        modules = [m for m in re.findall(r"python3? +-m +([\w.]+)", code)
                   if m != "pytest"]
        assert modules, "the gate runs nothing"
        for path in scripts:
            assert (REPO_ROOT / path).is_file(), \
                f"tier1.sh runs {path}, which is not in the tree"
        for mod in modules:
            base = REPO_ROOT / mod.replace(".", "/")
            assert base.with_suffix(".py").is_file() or \
                (base / "__main__.py").is_file(), \
                f"tier1.sh runs -m {mod}, which is not in the tree"
        i = code.index("-m pytest")
        pytest_line = code[i:code.index("\n\n", i)]
        for flag in ("-m 'not slow'", "-n 6", "--dist loadfile"):
            assert flag in pytest_line, \
                f"tier1.sh's pytest line lost {flag}"
