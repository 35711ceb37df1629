"""Host-side window lifecycle bookkeeping, shared by the single-device and
mesh-sharded window engines.

Owns the pieces of WindowOperator semantics that are pure host metadata
(reference: streaming/runtime/operators/windowing/WindowOperator.java —
isWindowLate at processElement:293, timer-driven firing at onEventTime:450,
allowed-lateness retention + cleanup timers at clearAllState): the
pending-window heap, the slice cleanup heap, late-record dropping, and the
fire/release ordering on watermark advance. The engines own only the state
arrays and the device math.

Allowed-lateness semantics (mirrors the reference): a window first fires when
the watermark passes its end; its slices are *retained* for ``lateness`` more
event-time ms. A late record landing in a retained slice re-schedules the
already-fired windows it contributes to, producing updated ("late firing")
results — note the vectorized engine re-emits the whole window's keys, not
just the late key. Records whose slices are past retention are dropped.
"""

from __future__ import annotations

import heapq
from typing import Dict, List, Optional, Set

import numpy as np

from flink_tpu.windowing.assigners import WindowAssigner

_NEG_INF = -(1 << 62)


class SliceBookkeeper:
    def __init__(self, assigner: WindowAssigner, allowed_lateness: int = 0):
        self.assigner = assigner
        self.allowed_lateness = allowed_lateness
        self._pending: List[int] = []
        self._pending_set: Set[int] = set()
        # slice end -> last participating window end (live slices)
        self._slice_last_window: Dict[int, int] = {}
        # (cleanup_time, slice_end): slice freed when watermark >= cleanup_time
        self._cleanup: List[tuple] = []
        self.watermark: int = _NEG_INF
        self.max_fired_end: int = _NEG_INF
        self.late_records_dropped = 0
        # (watermark, oldest_live_slice_end() at it)
        self._live_from = (_NEG_INF, _NEG_INF)

    # ---------------------------------------------------------------- arrivals

    def oldest_live_slice_end(self) -> int:
        """The smallest slice end :meth:`live_mask` keeps at the current
        watermark: a batch whose oldest slice end is at least this drops
        nothing, which is that method's early-out as a threshold — one
        scalar a native sweep can test a batch against. A slice's last
        window end never falls as the slice end grows, so the late slices
        are exactly those below it. Reckoned once per watermark."""
        wm = self.watermark
        if wm <= _NEG_INF // 2:
            return _NEG_INF
        if self._live_from[0] != wm:
            a, lateness = self.assigner, self.allowed_lateness
            w = a.slice_width
            # the slice of wm - lateness + 1 ends past it, so its own
            # end alone keeps it live; one more than size // w slices
            # below it even the longest reach (size - w past the slice
            # end) is over
            t = wm - lateness + 1
            top = t - (t - a.offset) % w + w
            ends = np.arange(top - (a.size // w + 1) * w, top + w, w,
                             dtype=np.int64)
            live = a.last_window_ends(ends) - 1 + lateness > wm
            self._live_from = (wm, int(ends[np.argmax(live)]))
        return self._live_from[1]

    def live_mask(self, slice_ends: np.ndarray) -> Optional[np.ndarray]:
        """Late-record filter: a record is dropped iff its slice is past
        retention (last window end - 1 + lateness <= current watermark).
        Returns a boolean mask if any record must be dropped, else None."""
        if self.watermark <= _NEG_INF // 2:
            return None
        # scalar early-out: the OLDEST slice in the batch decides whether a
        # full vectorized pass is needed at all — for in-order streams the
        # oldest slice is always live, so the common case costs one .min()
        # instead of three passes over the batch
        oldest = int(np.asarray(slice_ends).min())
        oldest_last = int(self.assigner.last_window_ends(
            np.asarray([oldest], dtype=np.int64))[0])
        if oldest_last - 1 + self.allowed_lateness > self.watermark:
            return None
        last_ends = self.assigner.last_window_ends(slice_ends)
        live = last_ends - 1 + self.allowed_lateness > self.watermark
        dropped = len(live) - int(live.sum())
        if dropped == 0:
            return None
        self.late_records_dropped += dropped
        return live

    def register_slices(self, slice_ends: np.ndarray,
                        uniq: Optional[np.ndarray] = None) -> bool:
        """Track new slices and (re-)schedule their windows.

        A window is scheduled iff it can still produce output:
        w - 1 + lateness > watermark. For an already-fired window inside the
        lateness allowance this is a late re-firing. ``uniq`` lets the
        caller supply the already-computed distinct slice ends (see
        WindowAssigner.slice_plan) instead of re-sorting the batch.

        True where a window at or under ``max_fired_end`` stands scheduled
        for one of these slices: the batch holds records behind a window
        that has fired (never on an in-order stream)."""
        lateness = self.allowed_lateness
        late = False
        if uniq is None:
            uniq = np.unique(slice_ends)
        for se in uniq.tolist():
            ends = None
            if se not in self._slice_last_window:
                ends = self.assigner.window_ends_for_slice(se)
                last = ends[-1]
                self._slice_last_window[se] = last
                heapq.heappush(self._cleanup, (last - 1 + lateness, se))
            elif lateness > 0:
                # existing slice: a late record may need to re-fire windows
                # that already fired
                ends = self.assigner.window_ends_for_slice(se)
            if ends is None:
                continue
            for w in ends:
                if w - 1 + lateness > self.watermark:
                    if w not in self._pending_set:
                        self._pending_set.add(w)
                        heapq.heappush(self._pending, w)
                    late = late or w <= self.max_fired_end
        return late

    # -------------------------------------------------------------------- fire

    def pending_windows(self) -> Set[int]:
        """Window ends currently scheduled to fire (a read-only view of
        the live set — do not mutate) — the set the pane pre-aggregation
        keeps a running partial row for (windowing/windower.py
        PaneWindower; includes late re-registrations). Consumers needing
        deterministic order sort it themselves (rebuild_window_partials
        does)."""
        return self._pending_set

    def next_window(self, watermark: int) -> Optional[int]:
        """Pop the next window due at ``watermark`` (end-1 <= watermark)."""
        self.watermark = max(self.watermark, watermark)
        if self._pending and self._pending[0] - 1 <= watermark:
            w_end = heapq.heappop(self._pending)
            self._pending_set.discard(w_end)
            return w_end
        return None

    def mark_fired(self, window_end: int) -> None:
        self.max_fired_end = max(self.max_fired_end, window_end)

    def expired_slices(self, watermark: int) -> List[int]:
        """Slices past retention at ``watermark`` — free their state.
        Call after the fire loop of the same watermark."""
        self.watermark = max(self.watermark, watermark)
        out: List[int] = []
        while self._cleanup and self._cleanup[0][0] <= watermark:
            _, se = heapq.heappop(self._cleanup)
            if se in self._slice_last_window:
                del self._slice_last_window[se]
                out.append(se)
        return out

    # ---------------------------------------------------------------- snapshot

    def snapshot(self) -> Dict[str, object]:
        return {
            "pending": sorted(self._pending),
            "slice_last_window": dict(self._slice_last_window),
            "watermark": self.watermark,
            "max_fired_end": self.max_fired_end,
            "late_records_dropped": self.late_records_dropped,
        }

    def merge_restore(self, snap: Dict[str, object]) -> None:
        """Partial-failover merge: fold a CHECKPOINT-time book into the
        LIVE book so a lost shard's key groups can replay their range.

        Rules (window metadata is global, unlike the per-key state):

        - registered slices = UNION — slices created by survivors after
          the checkpoint stay tracked; slices the checkpoint knew that
          already expired here re-register (their replayed re-fire emits
          only the restored range's keys: the survivors' rows are gone).
        - pending windows = UNION of live pending and the checkpoint's
          pending + every window of a re-registered slice that can still
          produce output AT THE CHECKPOINT watermark — a window fired
          between the checkpoint and the failure must RE-FIRE during
          replay (its restored-range rows were rolled back), and emits
          nothing for survivors (their slots were freed at the original
          fire).
        - watermark = the CHECKPOINT's — replayed records must pass the
          late-record guard exactly as they did originally; survivors
          are unaffected because replay feeds only the restored range,
          and the watermark monotonically re-advances with the replayed
          sequence.
        """
        self._slice_last_window.update(
            dict(snap.get("slice_last_window", {})))
        self._cleanup = [
            (last - 1 + self.allowed_lateness, se)
            for se, last in self._slice_last_window.items()
        ]
        heapq.heapify(self._cleanup)
        ckpt_wm = snap.get("watermark", snap.get("max_fired_end",
                                                 _NEG_INF))
        lateness = self.allowed_lateness
        for w in snap.get("pending", []):
            if w not in self._pending_set:
                self._pending_set.add(w)
                heapq.heappush(self._pending, w)
        # windows fired AFTER the checkpoint: pending in neither book,
        # but their slices are registered — re-schedule every window
        # still fireable at the checkpoint watermark
        for se in self._slice_last_window:
            for w in self.assigner.window_ends_for_slice(se):
                if (w - 1 + lateness > ckpt_wm
                        and w not in self._pending_set):
                    self._pending_set.add(w)
                    heapq.heappush(self._pending, w)
        self.watermark = ckpt_wm
        self.max_fired_end = min(
            self.max_fired_end,
            int(snap.get("max_fired_end", _NEG_INF)))

    def restore(self, snap: Dict[str, object]) -> None:
        # empty sub-structures may be pruned by the checkpoint codec
        self._pending = list(snap.get("pending", []))
        heapq.heapify(self._pending)
        self._pending_set = set(self._pending)
        self._slice_last_window = dict(snap.get("slice_last_window", {}))
        self._cleanup = [
            (last - 1 + self.allowed_lateness, se)
            for se, last in self._slice_last_window.items()
        ]
        heapq.heapify(self._cleanup)
        self.watermark = snap.get("watermark", snap.get("max_fired_end",
                                                        _NEG_INF))
        self.max_fired_end = snap.get("max_fired_end", _NEG_INF)
        self.late_records_dropped = snap.get("late_records_dropped", 0)
