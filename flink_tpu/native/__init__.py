"""Native (C++) runtime components, loaded via ctypes.

Build happens on demand with g++ (no pip deps): the shared object is cached
under ``native/build/`` next to a source-hash stamp, so editing a ``.cpp``
always triggers a rebuild (mtime alone lies after checkouts/copies). Set
``FLINK_TPU_NO_NATIVE=1`` to force the pure Python fallbacks (used in
tests to cover both paths): it is the one switch.

Every function fetched off a CDLL returned by :func:`load_native` must
declare ``argtypes`` AND ``restype`` before its first call — a missing
``restype`` silently truncates 64-bit returns (and pointers) to C int.
flint rule NAT01 enforces this statically against
:data:`NATIVE_SYMBOL_PREFIXES`.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Dict, Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_BUILD_DIR = os.path.join(_REPO_ROOT, "native", "build")

#: every exported symbol of every native library starts with one of
#: these — the registry flint's NAT01 cross-checks ctypes declarations
#: and call sites against (the stringly-typed-registry discipline of
#: chaos.KNOWN_FAULT_POINTS, applied to the C ABI)
NATIVE_SYMBOL_PREFIXES = ("sm_", "sx_", "codec_", "ngen_", "hc_")

#: hotcache symbols that MUTATE the arena — owner-side only. Frontends
#: attach with hc_attach and are read-only by contract (the seqlock
#: protects readers against a concurrent writer, not writer vs writer);
#: flint's SHM01 statically forbids any of these in an attach-rooted
#: scope. Keep this a plain literal tuple: flint parses it statically.
HOTCACHE_WRITER_SYMBOLS = ("hc_put_batch", "hc_prime_batch", "hc_drop",
                           "hc_clear", "hc_migrate", "hc_add_stat")

#: the libraries build_all() compiles (source basename -> .so basename)
NATIVE_LIBS = {
    "slotmap": ("slotmap.cpp", "_slotmap.so"),
    "sessions": ("sessions.cpp", "_sessions.so"),
    "codec": ("codec.cpp", "_codec.so"),
    "datagen": ("datagen.cpp", "_datagen.so"),
    "hotcache": ("hotcache.cpp", "_hotcache.so"),
}

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def native_disabled() -> bool:
    return os.environ.get("FLINK_TPU_NO_NATIVE") == "1"


#: count of LOUD degradations to a Python fallback plane (build
#: failure, load failure, runtime sweep error) — 0 on a healthy deploy.
#: The explicit opt-out (FLINK_TPU_NO_NATIVE=1) does NOT count: only
#: the cases where native was wanted and silently losing it would hide
#: a throughput regression behind a green suite.
_fallbacks = 0
_fallback_reasons: set = set()


def note_fallback(reason: str) -> None:
    """Record one native->Python degradation: warn once per distinct
    reason (so a per-engine construction loop cannot spam) and bump the
    :func:`native_fallbacks` counter."""
    global _fallbacks
    _fallbacks += 1
    if reason not in _fallback_reasons:
        _fallback_reasons.add(reason)
        import warnings

        warnings.warn(
            f"flink_tpu native plane degraded to Python fallback: "
            f"{reason}", RuntimeWarning, stacklevel=3)


def native_fallbacks() -> int:
    """Total native->Python degradations this process (see
    :func:`note_fallback`)."""
    return _fallbacks


def reset_fallbacks_for_testing() -> None:
    global _fallbacks
    _fallbacks = 0
    _fallback_reasons.clear()


_build_token: Optional[str] = None


def _build_provenance() -> str:
    """Compiler + host token folded into the artifact stamp: the build
    uses ``-march=native``, so an artifact is only valid for the
    (toolchain, CPU) that produced it — a copied build/ directory from
    a newer microarchitecture would otherwise load and SIGILL
    mid-suite. Cached per process (one g++ subprocess)."""
    global _build_token
    if _build_token is None:
        try:
            gxx = subprocess.run(["g++", "-dumpfullversion"],
                                 capture_output=True, timeout=10,
                                 text=True).stdout.strip()
        except Exception:
            gxx = "unknown"
        cpu = ""
        try:
            with open("/proc/cpuinfo") as f:
                for line in f:
                    if line.startswith("model name"):
                        cpu = line.split(":", 1)[1].strip()
                        break
        except OSError:
            pass
        import platform

        _build_token = f"g++={gxx};arch={platform.machine()};cpu={cpu}"
    return _build_token


def _source_hash(src: str) -> str:
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update(b"\x00" + _build_provenance().encode())
    return h.hexdigest()


def load_native(src_basename: str, so_basename: str) -> Optional[ctypes.CDLL]:
    """Compile-on-demand ctypes loader shared by every native component
    (slotmap, sessions, codec, datagen). Returns the CDLL, or None when
    disabled (FLINK_TPU_NO_NATIVE=1) or the
    toolchain/compile is unavailable.

    Staleness: the cached ``.so`` is paired with a ``.srchash`` stamp
    holding the sha256 of the source it was built from PLUS the build
    provenance (g++ version, machine, CPU model — the build uses
    ``-march=native``); a mismatch (or a missing stamp) forces a
    rebuild, so editing the ``.cpp`` can never load yesterday's binary
    and a build/ directory copied from a different host can never load
    the wrong microarchitecture's code — mtime comparison alone breaks
    under git checkouts and file copies that preserve timestamps. The
    compile is flock-guarded (concurrent processes build once) and
    writes to a temp name, os.replace()d into place — the .so first,
    the stamp after, so a crash between the two re-runs the build
    instead of trusting a half-updated pair.
    """
    if native_disabled():
        return None
    src = os.path.join(_REPO_ROOT, "native", src_basename)
    so_path = os.path.join(_BUILD_DIR, so_basename)
    if not os.path.exists(src):
        # sourceless deployment: a prebuilt artifact is all there is —
        # no staleness question to answer
        try:
            return ctypes.CDLL(so_path) if os.path.exists(so_path) else None
        except OSError:
            return None
    stamp_path = so_path + ".srchash"
    want_hash = _source_hash(src)

    def _stale() -> bool:
        if not os.path.exists(so_path):
            return True
        try:
            with open(stamp_path, "r") as f:
                return f.read().strip() != want_hash
        except OSError:
            return True  # stampless artifact: provenance unknown

    if _stale():
        os.makedirs(_BUILD_DIR, exist_ok=True)
        lock_path = so_path + ".lock"
        try:
            lock_f = open(lock_path, "w")
        except OSError:
            return None
        try:
            try:
                import fcntl

                fcntl.flock(lock_f, fcntl.LOCK_EX)
            except (ImportError, OSError):
                pass  # no flock (non-POSIX): fall back to tmp+rename only
            if _stale():  # a racing process may have built while we waited
                tmp = so_path + f".tmp.{os.getpid()}"
                cmd = ["g++", "-O3", "-march=native", "-shared", "-fPIC",
                       "-std=c++17", src, "-o", tmp]
                try:
                    r = subprocess.run(cmd, capture_output=True, timeout=120)
                    if r.returncode != 0 or not os.path.exists(tmp):
                        return None
                    os.replace(tmp, so_path)
                    stamp_tmp = stamp_path + f".tmp.{os.getpid()}"
                    with open(stamp_tmp, "w") as f:
                        f.write(want_hash)
                    os.replace(stamp_tmp, stamp_path)
                except Exception:
                    return None
        finally:
            lock_f.close()
    try:
        return ctypes.CDLL(so_path)
    except OSError:
        return None


def load_slotmap() -> Optional[ctypes.CDLL]:
    """The slotmap library, or None if unavailable/disabled."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        lib = load_native("slotmap.cpp", "_slotmap.so")
        if lib is None:
            return None
        c = ctypes
        i64, i32, u8, vp = (c.c_int64, c.c_int32, c.c_uint8, c.c_void_p)
        P = c.POINTER
        lib.sm_create.restype = vp
        lib.sm_create.argtypes = [i64, i64, i32]
        lib.sm_destroy.restype = None
        lib.sm_destroy.argtypes = [vp]
        lib.sm_capacity.restype = i64
        lib.sm_capacity.argtypes = [vp]
        lib.sm_used.restype = i64
        lib.sm_used.argtypes = [vp]
        lib.sm_slot_keys.restype = P(i64)
        lib.sm_slot_keys.argtypes = [vp]
        lib.sm_slot_namespaces.restype = P(i64)
        lib.sm_slot_namespaces.argtypes = [vp]
        lib.sm_slot_used.restype = P(u8)
        lib.sm_slot_used.argtypes = [vp]
        lib.sm_lookup_or_insert.restype = i32
        lib.sm_lookup_or_insert.argtypes = [vp, i64, P(i64), P(i64), P(i32),
                                            P(u8)]
        lib.sm_resolve_grouped.restype = i32
        lib.sm_resolve_grouped.argtypes = [vp, i64, P(i64), P(i64), i64, i64,
                                           i64, i64, P(i32), P(i64),
                                           P(i64)]
        lib.sm_resolve_grouped_sharded.restype = i32
        lib.sm_resolve_grouped_sharded.argtypes = [
            P(vp), i64, i64, P(i64), P(i64), P(i32), i64, i64, i64, i64,
            i64, P(u8), i64, P(i32), P(i32), P(i64), P(i64), P(i64)]
        lib.sm_erase.restype = i64
        lib.sm_erase.argtypes = [vp, i64, P(i64), P(i64), P(i32)]
        lib.sm_lookup.restype = None
        lib.sm_lookup.argtypes = [vp, i64, P(i64), P(i64), P(i32)]
        lib.sm_verify.restype = None
        lib.sm_verify.argtypes = [vp, i64, P(i64), P(i64), P(i32), P(i32)]
        lib.sm_carry_create.restype = vp
        lib.sm_carry_create.argtypes = []
        lib.sm_carry_destroy.restype = None
        lib.sm_carry_destroy.argtypes = [vp]
        lib.sm_carry_advance.restype = i64
        lib.sm_carry_advance.argtypes = [vp, vp, i64, P(i64), i64, P(i64),
                                         P(i32), P(i64), P(i64), P(i64)]
        lib.sm_drop_namespaces.restype = i64
        lib.sm_drop_namespaces.argtypes = [vp, i64, P(i64), P(i32)]
        lib.sm_namespace_count.restype = i64
        lib.sm_namespace_count.argtypes = [vp]
        lib.sm_namespaces.restype = None
        lib.sm_namespaces.argtypes = [vp, P(i64)]
        lib.sm_namespace_slots.restype = i64
        lib.sm_namespace_slots.argtypes = [vp, i64, P(i32), i64]
        lib.sm_pane_ingest.restype = i32
        lib.sm_pane_ingest.argtypes = [vp, i64, P(i64), P(i64), i64, i64,
                                       i64, P(i32), P(u8), P(i32), P(i64),
                                       P(i64), P(i64)]
        lib.sm_flat_fuse.restype = None
        lib.sm_flat_fuse.argtypes = [i64, P(i32), P(i32), P(i64), i64,
                                     P(i32)]
        lib.sm_group_rows.restype = i64
        lib.sm_group_rows.argtypes = [i64, P(i64), P(i64), P(i64)]
        _lib = lib
        return _lib


def slotmap_available() -> bool:
    return load_slotmap() is not None


_sessions_lib: Optional[ctypes.CDLL] = None
_sessions_tried = False


def load_sessions() -> Optional[ctypes.CDLL]:
    """The native session-metadata plane (native/sessions.cpp), or None.

    One fused C sweep per batch replaces the numpy hot loop of
    ``windowing/session_meta.py``: sessionize + absorb + fire-candidate
    maintenance in one pass, with the session's device slot folded into
    the metadata row (see flink_tpu/windowing/session_native.py).
    """
    global _sessions_lib, _sessions_tried
    with _lock:
        if _sessions_tried:
            return _sessions_lib
        _sessions_tried = True
        lib = load_native("sessions.cpp", "_sessions.so")
        if lib is None:
            return None
        c = ctypes
        i64, i32, u8, vp = (c.c_int64, c.c_int32, c.c_uint8, c.c_void_p)
        P = c.POINTER
        lib.sx_create.restype = vp
        lib.sx_create.argtypes = [i64, i64]
        lib.sx_destroy.restype = None
        lib.sx_destroy.argtypes = [vp]
        lib.sx_capacity.restype = i64
        lib.sx_capacity.argtypes = [vp]
        lib.sx_used.restype = i64
        lib.sx_used.argtypes = [vp]
        lib.sx_keys.restype = P(i64)
        lib.sx_keys.argtypes = [vp]
        lib.sx_starts.restype = P(i64)
        lib.sx_starts.argtypes = [vp]
        lib.sx_ends.restype = P(i64)
        lib.sx_ends.argtypes = [vp]
        lib.sx_sids.restype = P(i64)
        lib.sx_sids.argtypes = [vp]
        lib.sx_dslots.restype = P(i32)
        lib.sx_dslots.argtypes = [vp]
        lib.sx_used_mask.restype = P(u8)
        lib.sx_used_mask.argtypes = [vp]
        lib.sx_lookup.restype = None
        lib.sx_lookup.argtypes = [vp, i64, P(i64), P(i32)]
        lib.sx_insert.restype = i32
        lib.sx_insert.argtypes = [vp, i64, P(i64), P(i32)]
        lib.sx_erase_rows.restype = None
        lib.sx_erase_rows.argtypes = [vp, i64, P(i32)]
        lib.sx_lookup1.restype = i32
        lib.sx_lookup1.argtypes = [vp, i64]
        lib.sx_insert1.restype = i32
        lib.sx_insert1.argtypes = [vp, i64]
        lib.sx_erase1.restype = None
        lib.sx_erase1.argtypes = [vp, i32]
        lib.sx_multi_add.restype = None
        lib.sx_multi_add.argtypes = [vp, i64]
        lib.sx_multi_remove.restype = None
        lib.sx_multi_remove.argtypes = [vp, i64]
        lib.sx_multi_count.restype = i64
        lib.sx_multi_count.argtypes = [vp]
        for absorb in (lib.sx_absorb, lib.sx_absorb_sorted):
            absorb.restype = i64
            absorb.argtypes = [vp, i64, P(i64), P(i64),  # n, keys, ts
                               i64, i64, i64, i64,  # gap, late, mfw, sid
                               P(i64), P(i64),      # order, rec_to_sess
                               P(i32),              # rec_sess
                               P(i64), P(i64), P(i64), P(i64),  # k/s/e/sid
                               P(i32), P(i32), P(u8),  # slot/row/flags
                               P(i64)]              # out[5] scalars
        lib.sx_sorted_maps.restype = None
        lib.sx_sorted_maps.argtypes = [i64, i64, P(i32), P(i64), P(i64)]
        lib.sx_fold.restype = None
        lib.sx_fold.argtypes = [vp, i64, P(i64), P(i64), P(i32)]
        lib.sx_fold_rows.restype = None
        lib.sx_fold_rows.argtypes = [vp, i64, P(i32), P(i64), P(i32)]
        lib.sx_push_chunk.restype = None
        lib.sx_push_chunk.argtypes = [vp, i64, P(i64), P(i64), P(i64)]
        lib.sx_min_pending.restype = i64
        lib.sx_min_pending.argtypes = [vp]
        lib.sx_pop.restype = i64
        lib.sx_pop.argtypes = [vp, i64, P(i64)]
        lib.sx_pop_fetch.restype = None
        lib.sx_pop_fetch.argtypes = [vp, P(i64), P(i64), P(i64), P(i64),
                                     P(i32)]
        lib.sx_pop_fetch_rest.restype = None
        lib.sx_pop_fetch_rest.argtypes = [vp, P(i64), P(i64), P(i64)]
        lib.sx_shard_group.restype = i64
        lib.sx_shard_group.argtypes = [i64, P(i64), P(i64), P(u8), P(i32),
                                       P(i32), i64, i64, i64, i64,
                                       P(i64), P(i64), P(i64),
                                       P(i64), P(i64), P(u8), P(i32),
                                       P(i32)]
        lib.sx_route.restype = None
        lib.sx_route.argtypes = [i64, i64, P(i32), i64, P(i64),
                                 P(i32), P(i64), P(i32), P(i64)]
        lib.sx_rec_shard_max.restype = i64
        lib.sx_rec_shard_max.argtypes = [i64, P(i64), i64, i64, i64, i64]
        _sessions_lib = lib
        return _sessions_lib


def sessions_available() -> bool:
    return load_sessions() is not None


_datagen_lib: Optional[ctypes.CDLL] = None
_datagen_tried = False


def load_datagen() -> Optional[ctypes.CDLL]:
    """The native stream generator (native/datagen.cpp), or None."""
    global _datagen_lib, _datagen_tried
    with _lock:
        if _datagen_tried:
            return _datagen_lib
        _datagen_tried = True
        lib = load_native("datagen.cpp", "_datagen.so")
        if lib is None:
            return None
        c = ctypes
        i64, f32p = c.c_int64, c.POINTER(c.c_float)
        P = c.POINTER
        lib.ngen_bids.restype = None
        lib.ngen_bids.argtypes = [i64, i64, i64, i64, i64, i64, i64, i64,
                                  P(c.c_int64), P(c.c_int64), f32p,
                                  P(c.c_int64)]
        _datagen_lib = lib
        return _datagen_lib


_hotcache_lib: Optional[ctypes.CDLL] = None
_hotcache_tried = False

#: hc_stat counter indices (must match the Stat enum in hotcache.cpp)
HC_STAT_HITS = 0
HC_STAT_MISSES = 1
HC_STAT_EVICTIONS = 2
HC_STAT_PRIMES = 3
HC_STAT_PUTS = 4
HC_STAT_TORN_RETRIES = 5
HC_STAT_TORN_MISSES = 6
HC_STAT_OVERSIZE_DROPS = 7

#: per-frontend counter indices (must match the FeStat enum in
#: hotcache.cpp) — accumulated IN the shared arena header by attached
#: frontend processes (hc_fe_note), read owner-side without IPC
HC_FE_STAT_PROBES = 0
HC_FE_STAT_HITS = 1
HC_FE_STAT_TORN_RETRIES = 2
HC_FE_STAT_MISS_CROSSINGS = 3
HC_FE_STAT_NAMES = ("probes", "hits", "torn_retries", "miss_crossings")
#: fe_stats rows reserved in the arena header (kMaxFrontends)
HC_MAX_FRONTENDS = 64


def load_hotcache() -> Optional[ctypes.CDLL]:
    """The native hot-row probe table (native/hotcache.cpp), or None.

    One GIL-released C call probes/primes a whole key batch against an
    open-addressing, seqlock-stamped table of packed composed results —
    the serving hot loop of flink_tpu/tenancy/hot_cache_native.py
    (flink_tpu/tenancy/hot_cache.py stays the bit-identical Python
    fallback).
    """
    global _hotcache_lib, _hotcache_tried
    with _lock:
        if _hotcache_tried:
            return _hotcache_lib
        _hotcache_tried = True
        lib = load_native("hotcache.cpp", "_hotcache.so")
        if lib is None:
            return None
        c = ctypes
        i64, i32, u8, u64, vp = (c.c_int64, c.c_int32, c.c_uint8,
                                 c.c_uint64, c.c_void_p)
        P = c.POINTER
        lib.hc_create.restype = vp
        lib.hc_create.argtypes = [i64, i64, i64]
        # shared-memory arena family (r21): the owner creates the table
        # as a MAP_SHARED file arena; frontend processes attach the SAME
        # table and probe it lock-free (seqlock readers are address-free)
        lib.hc_create_shared.restype = vp
        lib.hc_create_shared.argtypes = [c.c_char_p, i64, i64, i64]
        lib.hc_attach.restype = vp
        lib.hc_attach.argtypes = [c.c_char_p]
        lib.hc_epoch.restype = i64
        lib.hc_epoch.argtypes = [vp]
        lib.hc_arena_bytes.restype = i64
        lib.hc_arena_bytes.argtypes = [vp]
        lib.hc_is_attached.restype = i64
        lib.hc_is_attached.argtypes = [vp]
        lib.hc_fe_note.restype = None
        lib.hc_fe_note.argtypes = [vp, i32, i64, i64, i64, i64]
        lib.hc_fe_stat.restype = i64
        lib.hc_fe_stat.argtypes = [vp, i32, i32]
        lib.hc_destroy.restype = None
        lib.hc_destroy.argtypes = [vp]
        lib.hc_len.restype = i64
        lib.hc_len.argtypes = [vp]
        lib.hc_capacity.restype = i64
        lib.hc_capacity.argtypes = [vp]
        lib.hc_stat.restype = i64
        lib.hc_stat.argtypes = [vp, i32]
        lib.hc_add_stat.restype = None
        lib.hc_add_stat.argtypes = [vp, i32, i64]
        lib.hc_clear.restype = None
        lib.hc_clear.argtypes = [vp]
        lib.hc_get_batch.restype = i64
        lib.hc_get_batch.argtypes = [vp, i64, P(i64), i64, P(u8),
                                     P(i32), P(i64), P(i64), P(i64),
                                     P(u64)]
        # the frontend variant: same probe + per-frontend attribution
        # folded in the same GIL-released call
        lib.hc_get_batch_fe.restype = i64
        lib.hc_get_batch_fe.argtypes = [vp, i32, i64, P(i64), i64,
                                        P(u8), P(i32), P(i64), P(i64),
                                        P(i64), P(u64)]
        lib.hc_put_batch.restype = i64
        lib.hc_put_batch.argtypes = [vp, i64, P(i64), P(i64), P(i64),
                                     P(i64), P(i64), P(u64)]
        lib.hc_prime_batch.restype = i64
        lib.hc_prime_batch.argtypes = [vp, i64, P(i64), i64, P(i64),
                                       P(i64), P(i64), P(u64), P(i64),
                                       P(i64), P(u8)]
        lib.hc_drop.restype = None
        lib.hc_drop.argtypes = [vp, i64]
        lib.hc_migrate.restype = i64
        lib.hc_migrate.argtypes = [vp, vp]
        # test-only: freeze/unfreeze a slot's seqlock stamp so the
        # torn-read retry path is deterministically coverable
        lib.hc_debug_lock_slot.restype = i64
        lib.hc_debug_lock_slot.argtypes = [vp, i64]
        lib.hc_debug_unlock_slot.restype = i64
        lib.hc_debug_unlock_slot.argtypes = [vp, i64]
        _hotcache_lib = lib
        return _hotcache_lib


def hotcache_available() -> bool:
    return load_hotcache() is not None


def build_all() -> Dict[str, bool]:
    """Compile every native library up front (CI calls this before the
    suite so a missing toolchain is LOUD, not a silent mid-suite
    fallback). Returns {name: available}."""
    return {name: load_native(src, so) is not None
            for name, (src, so) in NATIVE_LIBS.items()}


def build_report() -> str:
    """One status line for CI logs: ``NATIVE: built`` when every
    library compiled, else ``NATIVE: SKIPPED (...)`` naming why."""
    if native_disabled():
        return "NATIVE: SKIPPED (disabled via env)"
    built = build_all()
    if all(built.values()):
        return "NATIVE: built (" + ", ".join(sorted(built)) + ")"
    missing = sorted(n for n, ok in built.items() if not ok)
    return ("NATIVE: SKIPPED (no compiler or build failed: "
            + ", ".join(missing) + ")")
