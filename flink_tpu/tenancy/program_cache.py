"""Shared compiled-program cache: one XLA program family for N jobs.

The multi-tenant claim that "job K+1 pays zero steady-state compiles on
a warm cluster" rests on one property: the compiled step programs
(scatter / fire / reset / gather / put / merge, the serving-plane query
gathers, and the fused exchange+scatter family of the device data plane
— ``parallel/shuffle.py build_exchange_scatter``, keyed ``(device ids,
aggregate layout, valued)``) are keyed on WHAT they compute — ``(program
kind, device ids, aggregate layout)`` — never on WHO runs them. Shapes are handled
one level down by jax's own jit cache together with the engines'
sticky-bucket padding discipline, so the full effective key is
``(kind, layout, bucketed shapes, device ids)``; an engine identity, a
job id, or a per-instance lambda in the key would compile the whole
family once per job and erase the tenancy win.

This module is that cache's single home. It wraps the raw program dict
(previously ``sharded_windower._STEP_CACHE``) with per-job hit/miss
attribution so the tenancy layer can PROVE sharing: after job A warms
the cluster, job B's stats must show ``misses == 0`` (the steady-state
tests gate on the stronger runtime signal too — the recompile sentinel
— and read these stats for the diagnosis when it trips).

No engine imports here: the cache must be importable from the lowest
layers (parallel/, state/) without cycles.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Optional, Tuple

from flink_tpu.observe.lock_sentinel import named_lock


class SharedProgramCache:
    """Process-global registry of compiled program families.

    ``get_or_build(kind, key, builder)`` returns the cached program for
    ``(kind, key)`` or builds, stores and returns it. ``key`` must be
    hashable and must identify everything the compiled program closes
    over (device ids, aggregate layout) — and nothing else.

    Job attribution is cooperative: the tenancy session cluster brackets
    each job's scheduling quantum with :meth:`job_scope`, so any program
    built (or hit) inside it is charged to that job. Outside a scope,
    traffic lands on the ``None`` job (single-job runs). The scope is
    PER THREAD (a MiniCluster runs each job's executor on its own
    thread), and the at-most-once build contract holds across threads:
    two jobs racing to the same key cost one XLA compile, not two — the
    loser waits on the winner's per-key latch (the stall is exactly the
    compile the cache saved it), while traffic for other keys proceeds
    unstalled.
    """

    def __init__(self) -> None:
        #: the raw storage — exposed for compatibility shims only
        self.programs: Dict[Tuple[str, Any], Any] = {}
        self._tls = threading.local()
        #: one lock for storage + stats: hits hold it for a dict probe;
        #: BUILDS run outside it behind a per-key once-latch (an XLA
        #: compile takes seconds — holding the cache lock across it
        #: would stall every other thread's unrelated cache hits)
        self._lock = named_lock("tenancy.program_cache", reentrant=True)
        #: key -> Event for builds in flight (see get_or_build)
        self._building: Dict[Tuple[str, Any], threading.Event] = {}
        #: job -> {"hits": n, "misses": n}
        self._job_stats: Dict[Optional[str], Dict[str, int]] = {}

    # ------------------------------------------------------------ attribution

    @property
    def _job(self) -> Optional[str]:
        return getattr(self._tls, "job", None)

    def set_current_job(self, job: Optional[str]) -> Optional[str]:
        """Set the job charged for subsequent cache traffic ON THIS
        THREAD; returns the previous value (for restore)."""
        prev = getattr(self._tls, "job", None)
        self._tls.job = job
        return prev

    def job_scope(self, job: Optional[str]):
        """Context manager form of :meth:`set_current_job`."""
        import contextlib

        @contextlib.contextmanager
        def _scope():
            prev = self.set_current_job(job)
            try:
                yield self
            finally:
                self.set_current_job(prev)

        return _scope()

    def _charge(self, field: str) -> None:
        st = self._job_stats.setdefault(self._job,
                                        {"hits": 0, "misses": 0})
        st[field] += 1

    # ----------------------------------------------------------------- lookup

    def get_or_build(self, kind: str, key: Any,
                     builder: Callable[[], Any]) -> Any:
        """The cached program family for ``(kind, key)``, building it on
        first use. The builder runs at most once per key for the process
        lifetime — restarted jobs, rescaled engines, NEW JOBS, and
        concurrent executor threads all hit. Two threads racing the SAME
        key cost one compile (the loser waits on the winner's latch and
        takes the cached result); a thread hitting a DIFFERENT key is
        never stalled by an in-flight build — the builder runs outside
        the cache lock. A failed build releases its latch so the next
        caller retries."""
        full = (kind, key)
        while True:
            with self._lock:
                cached = self.programs.get(full)
                if cached is not None:
                    self._charge("hits")
                    return cached
                latch = self._building.get(full)
                if latch is None:
                    self._building[full] = latch = threading.Event()
                    self._charge("misses")
                    break
            # another thread is compiling this key: wait, then re-probe
            # (on its failure we become the next builder)
            latch.wait()
        try:
            built = builder()
        except BaseException:
            # flint: disable=LCK03 -- latch protocol: the thread that
            # installed the latch above is its sole owner; no other
            # thread deletes this key's latch, so the boundary is safe
            with self._lock:
                del self._building[full]
            latch.set()
            raise
        # flint: disable=LCK03 -- latch protocol: this thread won the
        # builder election under the first hold and is the only writer
        # of this key until it sets the latch; waiters re-probe in the
        # while-loop, so the release boundary cannot lose an update
        with self._lock:
            self.programs[full] = built
            del self._building[full]
        latch.set()
        return built

    # ------------------------------------------------------------------ stats

    def stats(self) -> Dict[str, int]:
        with self._lock:
            hits = sum(s["hits"] for s in self._job_stats.values())
            misses = sum(s["misses"] for s in self._job_stats.values())
            return {"programs": len(self.programs),
                    "hits": hits, "misses": misses}

    def stat(self, field: str) -> int:
        """One stats() field without computing the others — what the
        per-scrape gauges read."""
        with self._lock:
            if field == "programs":
                return len(self.programs)
            return sum(s[field] for s in self._job_stats.values())

    def stats_for(self, job: Optional[str]) -> Dict[str, int]:
        """Per-job cache traffic ({"hits": n, "misses": n}); zeros for a
        job that never touched the cache."""
        with self._lock:
            return dict(self._job_stats.get(job,
                                            {"hits": 0, "misses": 0}))

    def reset_stats(self) -> None:
        """Clear attribution counters (NOT the programs — compiled
        executables stay shared; tests reset between phases)."""
        with self._lock:
            self._job_stats.clear()


#: THE process-global instance every engine routes through.
PROGRAM_CACHE = SharedProgramCache()
