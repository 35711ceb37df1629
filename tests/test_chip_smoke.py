"""chip_smoke.py held to its contract without a chip.

(i) its phase functions — the two one-chip jobs and the 4-shard mesh job
— run at tiny sizes on the virtual CPU mesh against their own host
references; (ii) the script itself, run as a child where JAX finds no
accelerator, exits non-zero and still ends its standard output with the
one line the driver reads; (iii) the function that writes that line
gives it exactly the agreed keys on the success path too.
"""

import io
import json
import os
import shutil
import subprocess
import sys
import threading

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_Q5 = dict(events=400_000, num_auctions=1_000, rate=10_000,
               capacity=1 << 16, batch=1 << 13)


def _assert_verdict_shape(line):
    v = json.loads(line)
    assert set(v) == {"ok", "device"}
    assert set(v["device"]) == {"platform", "kind", "count"}
    assert isinstance(v["ok"], bool)
    assert isinstance(v["device"]["platform"], str)
    assert isinstance(v["device"]["kind"], str)
    assert isinstance(v["device"]["count"], int)
    return v


# ------------------------------------------------------ (i) the phases


def test_q5_job_matches_its_reference_on_cpu():
    stats = chip_smoke.run_q5(22, platform="cpu", **TINY_Q5)
    assert stats["engine"] == "SliceSharedWindower"
    assert stats["windows_fired"] == 24
    assert stats["compiles_in_last_third_of_stream"] == 0


def test_keyed_state_job_matches_its_reference_on_cpu():
    stats = chip_smoke.run_keyed_state(
        22, events=400_000, num_keys=50_000, rate=10_000,
        capacity=1 << 18, batch=1 << 13, platform="cpu")
    assert stats["windows_fired"] == 8
    assert stats["result_rows"] > stats["max_live_slots_in_a_window"] > 0


def test_mesh_job_matches_its_reference_on_four_cpu_shards():
    stats = chip_smoke.run_q5_mesh(22, platform="cpu", chips=4, **TINY_Q5)
    assert stats["rank_kernel"]["interpreted"]  # the cpu backend only
    for backend in ("xla", "pallas"):
        assert stats[backend]["engine"] == "MeshWindowEngine"
        assert len(stats[backend]["state_on"]) == 4


def test_q5_reference_differs_when_the_seed_does():
    """The reference is made from ``--seed``: another seed, other rows
    (so a job that ignored the data could not match by accident)."""
    sizes = dict(events=50_000, num_auctions=100, rate=10_000,
                 size_ms=10_000, slide_ms=2_000)
    assert chip_smoke.q5_reference(1, **sizes) \
        != chip_smoke.q5_reference(2, **sizes)


# ------------------------------------------- (ii) the script as a child


@pytest.mark.parametrize("where", ["in_repo", "alone"])
def test_script_fails_with_readable_last_line_without_a_chip(
        where, tmp_path):
    if where == "alone":  # a directory that holds the script and nothing
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    else:
        cwd = REPO
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.endswith("\n")
    lines = proc.stdout.splitlines()
    for line in lines:  # nothing but the script's own JSON lines
        json.loads(line)
    v = _assert_verdict_shape(lines[-1])  # and nothing after it
    assert v["ok"] is False
    assert v["device"]["platform"] == "cpu"
    assert "chip_smoke needs a TPU" in proc.stderr


# --------------------------------------- (iii) the one verdict function


class _Left(Exception):
    pass


@pytest.mark.parametrize("ok, device, code", [
    (True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 0),
    (True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}, 0),
    (False, {"platform": "cpu", "kind": "cpu", "count": 8}, 1),
    (False, chip_smoke.NO_DEVICE, 1),
])
def test_verdict_line_has_exactly_the_agreed_keys(monkeypatch, ok, device,
                                                  code):
    left = []

    def leave(rc):
        left.append(rc)
        raise _Left

    monkeypatch.setattr(chip_smoke.os, "_exit", leave)
    monkeypatch.setattr(chip_smoke, "_verdict_once", threading.Lock())
    out = io.StringIO()
    with pytest.raises(_Left):
        chip_smoke.verdict(out, ok, device)
    assert left == [code]
    assert out.getvalue().count("\n") == 1
    v = _assert_verdict_shape(out.getvalue())
    assert v == {"ok": ok, "device": dict(device)}
    if ok:
        assert out.getvalue() == (
            '{"ok": true, "device": {"platform": "tpu", "kind": '
            '"TPU v5 lite", "count": %d}}\n' % device["count"])
