"""Pluggable program backends for the state-plane families.

Every family resolves to its XLA-idiom implementation by default; a
per-family override (``stateplane.backend.<family>=pallas|xla`` in the
job configuration, or :func:`set_backend` / :func:`backend_scope` in
process scope) swaps in an alternative kernel BEHIND the same builder
entry points. Two invariants make the swap safe:

- **Bit identity**: an alternative backend must be A/B gated
  bit-identical to the XLA program it replaces (values, emission
  order, downstream fold order) before it may ship. The gate for the
  first Pallas kernel lives in ``tools/pallas_ab_gate.py`` and
  ``tests/test_stateplane.py``.
- **Cache-key honesty**: builders resolve the backend at BUILD time
  and tag their PROGRAM_CACHE keys with it (see
  ``shuffle.build_exchange_scatter`` and friends), so a swap is a new
  cache entry — never a silent retrace of an existing key, and the
  zero-steady-state-recompile contract holds per backend.

Only ``exchange-rank`` has a non-XLA implementation today; requesting
``pallas`` for any other family raises loudly instead of silently
running XLA (a config typo must not vacuously pass an A/B experiment).
"""

from __future__ import annotations

import contextlib
from typing import Dict

from flink_tpu.observe.lock_sentinel import named_lock

#: families with a real alternative implementation, by backend name
_PALLAS_CAPABLE = ("exchange-rank",)

_VALID_BACKENDS = ("xla", "pallas")

_lock = named_lock("stateplane.backends")
_overrides: Dict[str, str] = {}

_CONFIG_PREFIX = "stateplane.backend."


def _validate(family: str, backend: str) -> str:
    from flink_tpu.stateplane.families import KNOWN_PROGRAM_FAMILIES

    if family not in KNOWN_PROGRAM_FAMILIES:
        raise ValueError(f"unknown program family {family!r}")
    if backend not in _VALID_BACKENDS:
        raise ValueError(f"unknown backend {backend!r} for family "
                         f"{family!r} (valid: {_VALID_BACKENDS})")
    if backend == "pallas" and family not in _PALLAS_CAPABLE:
        raise ValueError(
            f"family {family!r} has no pallas implementation yet "
            f"(pallas-capable: {_PALLAS_CAPABLE}) — the backend hook "
            "must not silently fall back to xla")
    return backend


def backend_of(family: str) -> str:
    """The backend the NEXT build of ``family`` resolves to."""
    with _lock:
        return _overrides.get(family, "xla")


def _set_locked(family: str, backend: str) -> None:
    """Install one override. Caller holds ``_lock``."""
    if backend == "xla":
        _overrides.pop(family, None)
    else:
        _overrides[family] = backend


def set_backend(family: str, backend: str) -> None:
    """Process-scope override (the config hook calls through here)."""
    _validate(family, backend)
    with _lock:
        _set_locked(family, backend)


@contextlib.contextmanager
def backend_scope(family: str, backend: str):
    """Scoped override — the A/B gates swap backends under this.

    Entry reads the previous value and installs the override under ONE
    lock hold; exit restores under one hold and only after re-checking
    that the override is still the one this scope installed. A
    concurrent :func:`set_backend` mid-scope therefore wins and
    survives the exit — the naive read/set/.../restore shape let the
    exit silently clobber it (the check-then-act race LCK03 flags)."""
    _validate(family, backend)
    with _lock:
        prev = _overrides.get(family, "xla")
        _set_locked(family, backend)
    try:
        yield
    finally:
        with _lock:
            if _overrides.get(family, "xla") == backend:
                _set_locked(family, prev)


def configure_backends(config) -> Dict[str, str]:
    """Apply every ``stateplane.backend.<family>`` key of a job
    configuration; returns the applied overrides. Unknown families and
    backends raise (same loudness as :func:`set_backend`) — the key
    space is SCANNED for the prefix, not probed per known family, so a
    typo'd family key fails instead of being silently ignored."""
    from flink_tpu.stateplane.families import KNOWN_PROGRAM_FAMILIES

    try:
        candidates = [k for k in config.keys()
                      if k.startswith(_CONFIG_PREFIX)]
    except AttributeError:  # duck-typed config without key iteration
        candidates = [_CONFIG_PREFIX + f for f in KNOWN_PROGRAM_FAMILIES]
    applied: Dict[str, str] = {}
    for key in candidates:
        raw = config.get_raw(key, None)
        if raw is None:
            continue
        family = key[len(_CONFIG_PREFIX):]
        set_backend(family, str(raw))
        applied[family] = str(raw)
    return applied
