"""Device watchdog: deadline-tracked device interactions + shard
quarantine — the DETECTION half of partial failover.

reference: the reference detects a dead TaskManager through heartbeat
timeouts (flink-runtime HeartbeatManager / TaskManagerRunner) and scopes
the restart to the failed pipelined region
(RestartPipelinedRegionFailoverStrategy). The mesh engines' analog of a
TaskManager is a SHARD (one device + its host-side slice of state), and
its "heartbeats" are the device interactions the engine performs anyway:
dispatch fences, fire harvests, batched ``device_get`` reads, serving
lookups. This module wraps those in deadline-tracked sections.

Design (micro-batch discipline):

- **Sections** (:meth:`DeviceWatchdog.section`) time one device
  interaction. A section that exceeds ``deadline_ms`` records a MISS —
  it never raises mid-interaction, because the engine may be half way
  through a batch whose partial effects on *surviving* shards could not
  be rolled back shard-locally.
- **Boundary probes** (:meth:`DeviceWatchdog.boundary_probe`) run at
  batch boundaries (top of ``process_batch`` / ``on_watermark``), where
  the engine is consistent at a known source position. The probe (a)
  fires the chaos ``device.lost`` fault point once per live shard, so a
  seeded plan can kill an exact shard at an exact boundary, and (b)
  escalates accumulated deadline misses: timeout -> retry (the next
  sections get another chance, with the same escalating-attempt
  bookkeeping ``run_recoverable`` uses) -> declare dead once the miss
  budget is spent. Declaring a shard dead quarantines it and raises
  :class:`ShardFailedError` — the signal the partial-failover path
  (``chaos.harness.run_shard_loss_verify``, and the executors' restart
  handling) consumes.
- Heartbeat gauges live in the job metric tree under a ``watchdog``
  group (:meth:`register_metrics`).

A real (non-injected) device failure surfaces as an exception from the
device interaction itself; callers translate it to a shard failure with
:meth:`declare_dead` where the failing shard is identifiable, and fall
back to whole-job restart where it is not.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

from flink_tpu.chaos import injection as chaos


class ShardFailedError(RuntimeError):
    """A mesh shard was declared dead (device lost or persistently past
    its deadline). Recovery is SHARD-GRANULAR: survivors keep their live
    state; only the failed shard's key groups restore from its
    checkpoint unit and replay their range of the stream."""

    def __init__(self, shard: int, reason: str) -> None:
        super().__init__(
            f"shard {shard} declared dead: {reason} — partial failover "
            "(restore only that shard's key groups, replay only its "
            "range)")
        self.shard = int(shard)
        self.reason = reason


class HostFailedError(ShardFailedError):
    """A whole HOST (one process's slice of the mesh — ``local_devices``
    shards, one contiguous key-group range) was declared dead: the
    chaos ``host.lost`` point fired, or every one of the host's shards
    uniformly ran past the deadline-miss budget while other hosts
    stayed healthy — the attribution signature of a lost process /
    severed DCN link, not of one wedged chip. Recovery is
    HOST-GRANULAR: survivors evacuate, the dead host's ``k`` shard
    units restore, its contiguous range replays (bounded by the
    per-host share of the stream)."""

    def __init__(self, host: int, shards, reason: str) -> None:
        self.host = int(host)
        self.shards = tuple(int(s) for s in shards)
        # ShardFailedError compat: .shard carries the first member so
        # shard-granular consumers still attribute SOMETHING sensible
        RuntimeError.__init__(
            self,
            f"host {host} declared dead (shards "
            f"{list(self.shards)}): {reason} — host failover (restore "
            "that host's key-group ranges, replay only its span)")
        self.shard = self.shards[0] if self.shards else -1
        self.reason = reason


class MeshStalledError(RuntimeError):
    """EVERY live shard is past its deadline-miss budget at once.

    The engines' device programs are SPMD — whole-mesh sections charge
    a miss to every shard, so a uniform streak carries NO shard
    attribution. Quarantining an arbitrary shard (e.g. shard 0) would
    evacuate a healthy device onto the actually-wedged one and burn the
    loss budget on wrong-shard failovers; the honest escalation is a
    WHOLE-JOB failure (restart strategy -> full restore), which this
    error routes to. Shard-granular deadline attribution needs
    per-shard sections (``section(op, shard=k)``) — serving probes or
    per-device harvests."""


class _Section:
    """One timed device interaction (slotted: sections sit on
    per-batch paths)."""

    __slots__ = ("_wd", "_op", "_shard", "_t0")

    def __init__(self, wd: "DeviceWatchdog", op: str, shard: int) -> None:
        self._wd = wd
        self._op = op
        self._shard = shard

    def __enter__(self) -> "_Section":
        self._t0 = self._wd._clock()
        # an injected slow device: a `delay`-kind rule here stretches
        # the section past its deadline, which is exactly how a real
        # wedged device program manifests (no exception — just time)
        chaos.fault_point("watchdog.deadline", op=self._op,
                          shard=self._shard)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self._wd._observe(self._op, self._shard,
                          self._wd._clock() - self._t0,
                          failed=exc_type is not None)


class _NullSection:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


NULL_SECTION = _NullSection()


class DeviceWatchdog:
    """Deadline policy + shard health for one engine's device mesh.

    ``deadline_ms``: a section slower than this records a miss
    (0 disables deadline tracking — sections still heartbeat).
    ``max_misses``: consecutive deadline misses a shard survives before
    the next boundary probe declares it dead (the timeout -> retry ->
    declare-dead escalation; each miss is one spent "retry attempt",
    the same budget shape ``run_recoverable``'s strategy counts).
    A successful in-deadline section resets the shard's streak.
    """

    def __init__(self, num_shards: int, deadline_ms: float = 0.0,
                 max_misses: int = 3,
                 clock: Callable[[], float] = time.perf_counter,
                 device_ids: Optional[List[int]] = None) -> None:
        self.deadline_ms = float(deadline_ms)
        self.max_misses = max(int(max_misses), 1)
        self._clock = clock
        self.quarantined: set = set()
        #: PHYSICAL device ids ever quarantined (when the engine told
        #: us the shard->device mapping via rebind) — the cross-job
        #: dedup key: N tenants sharing a mesh each quarantine the same
        #: dead device, and the arbiter must count it ONCE, not N times
        self.quarantined_devices: set = set()
        self.sections_timed = 0
        self.deadline_misses = 0
        self.declared_dead = 0
        self.rebind(num_shards, device_ids)

    # ----------------------------------------------------------- lifecycle

    def rebind(self, num_shards: int,
               device_ids: Optional[List[int]] = None) -> None:
        """Point the watchdog at a rebuilt mesh of ``num_shards`` shards
        (after a partial failover the survivors renumber 0..P-2).
        Cumulative counters and the quarantine HISTORY (incl. device
        ids) survive; per-shard streaks reset with the new numbering.
        ``device_ids``: the shard->physical-device mapping, when the
        engine knows it."""
        self.num_shards = int(num_shards)
        now = self._clock()
        self._misses: List[int] = [0] * self.num_shards
        self._last_beat: List[float] = [now] * self.num_shards
        self._device_ids = (list(device_ids)
                            if device_ids is not None else None)
        self.quarantined = set()
        t = getattr(self, "_topology", None)
        if t is not None and t.num_shards != self.num_shards:
            # a failover/reshard renumbered the shards: the (hosts,
            # local) mapping no longer applies — host attribution is
            # off until an engine re-declares a topology
            self._topology = None

    #: HostTopology for HOST-granular escalation (None = shard-only)
    _topology = None

    def set_topology(self, topology) -> None:
        """Teach the watchdog the mesh's (hosts, local) factorization:
        the boundary probe then (a) fires the chaos ``host.lost`` point
        once per live host and (b) escalates a miss streak that
        uniformly covers exactly one host's shards — while other hosts
        stay healthy — to :class:`HostFailedError` instead of picking
        one member shard."""
        if topology is not None:
            topology.check_covers(self.num_shards)
        self._topology = topology

    # ------------------------------------------------------------ sections

    def section(self, op: str, shard: int = -1) -> _Section:
        """Context manager timing one device interaction. ``shard=-1``
        for whole-mesh programs (a miss then counts against every live
        shard — the mesh runs SPMD, so a wedged program implicates the
        mesh until a shard-attributable signal arrives)."""
        return _Section(self, op, shard)

    def _observe(self, op: str, shard: int, seconds: float,
                 failed: bool = False) -> None:
        self.sections_timed += 1
        now = self._clock()
        targets = ([shard] if 0 <= shard < self.num_shards
                   else range(self.num_shards))
        missed = (self.deadline_ms > 0
                  and seconds * 1000.0 > self.deadline_ms)
        for p in targets:
            if missed:
                self._misses[p] += 1
                self.deadline_misses += 1
            elif not failed:
                self._misses[p] = 0
                self._last_beat[p] = now
        if missed:
            # one instant per missed section (not per implicated shard:
            # a whole-mesh section carries no shard attribution) — lands
            # in the flight-recorder timeline next to the batch/fire
            # spans that were running when the device went quiet
            from flink_tpu.observe import flight_recorder as flight

            flight.instant("watchdog.miss",
                           shard=shard if 0 <= shard < self.num_shards
                           else -1)

    # ------------------------------------------------------------- boundary

    def boundary_probe(self) -> None:
        """The batch-boundary health check — the ONLY place a shard is
        declared dead, so the raising point always sees an engine that
        is consistent at a known source position (the micro-batch analog
        of failing over at a barrier, not mid-record)."""
        topo = self._topology
        if chaos.armed():
            if topo is not None:
                for h in range(topo.num_hosts):
                    members = [p for p in topo.shards_of_host(h)
                               if p not in self.quarantined]
                    if not members:
                        continue
                    try:
                        chaos.fault_point("host.lost", host=h)
                    except chaos.InjectedFault as f:
                        self.declare_host_dead(
                            h, members, f"host.lost injected ({f})")
            for p in range(self.num_shards):
                if p in self.quarantined:
                    continue
                try:
                    chaos.fault_point("device.lost", shard=p)
                except chaos.InjectedFault as f:
                    self.declare_dead(p, f"device.lost injected ({f})")
        live = [p for p in range(self.num_shards)
                if p not in self.quarantined]
        offenders = [p for p in live
                     if self._misses[p] >= self.max_misses]
        if not offenders:
            return
        if len(offenders) == len(live) and len(live) > 1:
            # uniform streak from whole-mesh (SPMD) sections: no shard
            # attribution exists — escalate to a WHOLE-JOB failure
            # instead of quarantining an arbitrary healthy device
            raise MeshStalledError(
                f"all {len(live)} live shards are past the deadline-"
                f"miss budget ({self.max_misses} misses at "
                f"{self.deadline_ms} ms) — mesh-wide stall, no shard "
                "attribution: whole-job restart")
        if topo is not None:
            # HOST escalation: a streak that uniformly covers EXACTLY
            # one host's live shards — no offenders anywhere else — is
            # the signature of a lost PROCESS (or severed DCN link),
            # not one wedged chip: declare the host, not a member. A
            # streak that spills outside one host carries mixed
            # attribution and stays shard-granular below.
            off = set(offenders)
            for h in range(topo.num_hosts):
                members = {p for p in topo.shards_of_host(h)
                           if p in live}
                if members and off == members:
                    self.declare_host_dead(
                        h, sorted(members),
                        f"uniform deadline-miss streak across all "
                        f"{len(members)} live shards of host {h} "
                        f"(budget {self.max_misses}, deadline "
                        f"{self.deadline_ms} ms)")
        p = offenders[0]
        self.declare_dead(
            p, f"{self._misses[p]} consecutive deadline misses "
               f"(budget {self.max_misses}, deadline "
               f"{self.deadline_ms} ms)")

    def declare_dead(self, shard: int, reason: str) -> None:
        self.quarantined.add(int(shard))
        if self._device_ids is not None \
                and 0 <= int(shard) < len(self._device_ids):
            self.quarantined_devices.add(self._device_ids[int(shard)])
        self.declared_dead += 1
        raise ShardFailedError(int(shard), reason)

    def declare_host_dead(self, host: int, shards,
                          reason: str) -> None:
        """Quarantine every shard of ``host`` at once and raise the
        host-granular failure (the escalation ladder's HOST level)."""
        for p in shards:
            self.quarantined.add(int(p))
            if self._device_ids is not None \
                    and 0 <= int(p) < len(self._device_ids):
                self.quarantined_devices.add(self._device_ids[int(p)])
        self.declared_dead += 1
        self.hosts_declared_dead += 1
        raise HostFailedError(int(host), shards, reason)

    #: hosts declared dead over the watchdog's lifetime
    hosts_declared_dead = 0

    # -------------------------------------------------------------- signals

    def available(self, total_devices: int) -> int:
        """Devices usable for (re)scaling: a quarantined shard's device
        is out of the budget until an operator replaces it — the signal
        the autoscale bound clamping subtracts."""
        return max(int(total_devices) - len(self.quarantined), 1)

    def heartbeat_age_s(self) -> float:
        """Age of the STALEST live shard's last healthy interaction."""
        now = self._clock()
        ages = [now - self._last_beat[p] for p in range(self.num_shards)
                if p not in self.quarantined]
        return max(ages) if ages else 0.0

    def misses_by_shard(self) -> Dict[int, int]:
        return {p: m for p, m in enumerate(self._misses) if m}

    def register_metrics(self, group) -> None:
        """Heartbeat/health gauges under ``<scope>.watchdog``."""
        g = group.add_group("watchdog")
        g.gauge("sections_timed", lambda: self.sections_timed)
        g.gauge("deadline_misses", lambda: self.deadline_misses)
        g.gauge("shards_quarantined", lambda: len(self.quarantined))
        g.gauge("declared_dead", lambda: self.declared_dead)
        g.gauge("hosts_declared_dead",
                lambda: self.hosts_declared_dead)
        g.gauge("heartbeat_age_s", lambda: self.heartbeat_age_s())


def watchdog_from_config(config, num_shards: int
                         ) -> Optional[DeviceWatchdog]:
    """Build a watchdog from ``watchdog.*`` config, or None when
    disabled (the default — sections then cost one attribute check)."""
    from flink_tpu.core.config import WatchdogOptions

    if not config.get(WatchdogOptions.ENABLED):
        return None
    return DeviceWatchdog(
        num_shards,
        deadline_ms=config.get(WatchdogOptions.DEADLINE_MS),
        max_misses=config.get(WatchdogOptions.MAX_MISSES))
