"""Start-up: the compile cache is placed from outside, and nothing hides
the device.

``env.execute()`` runs on the devices JAX gives it and fails when JAX
fails: no probe child, no fallback to another platform, no silently
smaller mesh, no counter that reads 0 because its hook did not take."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import flink_tpu.platform as platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    """The keys ``enable_compilation_cache`` sets through jax.config,
    from a fresh (not yet enabled) module state."""
    seen = {}
    monkeypatch.setattr(platform, "_cache_enabled", False)
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: seen.__setitem__(key, value))
    return seen


def test_cache_dir_is_not_set_in_code_when_the_environment_sets_it(
        monkeypatch, config_updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    platform.enable_compilation_cache()
    assert config_updates  # thresholds are still set
    assert "jax_compilation_cache_dir" not in config_updates


def test_default_cache_dir_is_fixed_inside_the_checkout(
        monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    platform.enable_compilation_cache()
    assert config_updates["jax_compilation_cache_dir"] == \
        os.path.join(REPO, ".jax_cache")


def test_default_cache_dir_is_identical_across_two_processes():
    """The path is part of JAX's cache key: never temporary, pid- or
    time-derived."""
    code = ("from flink_tpu.platform import *\n"
            "enable_compilation_cache()\n"
            "print(compilation_cache_dir())\n")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    seen = [subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                           capture_output=True, text=True, timeout=120,
                           check=True).stdout.strip() for _ in range(2)]
    assert seen == [os.path.join(REPO, ".jax_cache")] * 2


def test_platform_module_is_the_cache_set_up_and_nothing_else():
    public = {n for n in vars(platform) if not n.startswith("_")}
    assert public == {"annotations", "os", "enable_compilation_cache",
                      "compilation_cache_dir"}


def _tiny_job(conf=None):
    from flink_tpu import Configuration, StreamExecutionEnvironment
    from flink_tpu.connectors.sinks import CollectSink
    from flink_tpu.connectors.sources import DataGenSource
    from flink_tpu.runtime.watermarks import WatermarkStrategy
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    env = StreamExecutionEnvironment(Configuration(conf or {}))
    sink = CollectSink()
    env.add_source(DataGenSource(total_records=100, num_keys=3,
                                 events_per_second_of_eventtime=100),
                   WatermarkStrategy.for_bounded_out_of_orderness(0)) \
        .key_by("key").window(TumblingEventTimeWindows.of(1000)) \
        .sum("value").sink_to(sink)
    env.execute()
    return sink


def test_execute_starts_no_child_process(monkeypatch):
    """A parent that has touched JAX holds the chip; a child that needs
    it then fails — so ``env.execute()`` starts none."""
    from flink_tpu import native

    assert all(native.build_all().values())  # g++ runs here, not below
    started = []
    real_init = subprocess.Popen.__init__

    def spy(self, args, *a, **kw):
        started.append(args)
        return real_init(self, args, *a, **kw)

    monkeypatch.setattr(subprocess.Popen, "__init__", spy)
    monkeypatch.setattr(os, "fork", lambda: started.append("fork") or 0)
    assert len(_tiny_job().rows()) > 0
    assert started == []


def test_parallelism_beyond_the_devices_raises_with_both_counts():
    have = len(jax.devices())
    with pytest.raises(ValueError) as e:
        _tiny_job({"parallelism.default": have * 2})
    assert f"{have * 2}-device mesh" in str(e.value)
    assert f"only {have} device(s)" in str(e.value)


def test_sentinel_install_counts_a_device_get():
    from flink_tpu.observe import recompile_sentinel

    recompile_sentinel.install()
    before = recompile_sentinel.transfer_count()
    jax.device_get(jnp.arange(8))
    assert recompile_sentinel.transfer_count() == before + 1
