"""Allowed-lateness semantics: retention, late re-firing, no state leaks
(reference: WindowOperator allowedLateness + cleanup timers)."""

import numpy as np
import pytest

from flink_tpu.core.records import KEY_ID_FIELD, RecordBatch
from flink_tpu.windowing.aggregates import SumAggregate
from flink_tpu.windowing.assigners import (
    CumulativeEventTimeWindows,
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
)
from flink_tpu.windowing.windower import SliceSharedWindower


def kb(keys, values, ts):
    return RecordBatch.from_pydict(
        {KEY_ID_FIELD: np.asarray(keys, dtype=np.int64),
         "v": np.asarray(values, dtype=np.float32)},
        timestamps=ts)


def fired(batches):
    out = {}
    for b in batches:
        for r in b.to_rows():
            out[(r[KEY_ID_FIELD], r["window_start"], r["window_end"])] = r["sum_v"]
    return out


class TestAllowedLateness:
    def test_late_record_refires_window(self):
        w = SliceSharedWindower(TumblingEventTimeWindows.of(100),
                                SumAggregate("v"), capacity=1024,
                                allowed_lateness=50)
        w.process_batch(kb([1], [1.0], [10]))
        first = fired(w.on_watermark(99))
        assert first == {(1, 0, 100): 1.0}
        # late record within lateness -> updated (re-fired) result
        w.process_batch(kb([1], [2.0], [20]))
        refired = fired(w.on_watermark(120))
        assert refired == {(1, 0, 100): 3.0}
        # past retention (99 + 50) -> dropped
        w.process_batch(kb([1], [4.0], [30]))
        assert w.late_records_dropped == 0
        w.on_watermark(149)  # window cleanup at 99+50=149
        w.process_batch(kb([1], [8.0], [40]))
        assert w.late_records_dropped == 1
        assert fired(w.on_watermark(10**6)) == {}

    def test_zero_lateness_drops_immediately(self):
        w = SliceSharedWindower(TumblingEventTimeWindows.of(100),
                                SumAggregate("v"), capacity=1024)
        w.process_batch(kb([1], [1.0], [10]))
        w.on_watermark(99)
        w.process_batch(kb([1], [2.0], [20]))
        assert w.late_records_dropped == 1
        assert w.table.num_used == 0  # nothing retained

    def test_no_state_leak_with_lateness(self):
        """Slices must be freed once retention passes (the leak the review
        found: records admitted by lateness into slices whose windows all
        fired must not pin slots forever)."""
        w = SliceSharedWindower(SlidingEventTimeWindows.of(200, 100),
                                SumAggregate("v"), capacity=1024,
                                allowed_lateness=100)
        for step in range(20):
            t = step * 100
            w.process_batch(kb([1, 2], [1.0, 1.0], [t + 10, t + 20]))
            w.on_watermark(t + 50)
        w.on_watermark(20 * 100 + 1000)
        assert w.table.num_used == 0
        assert not w.book._slice_last_window

    def test_cumulate_no_leak(self):
        """Cumulate's last_window_ends must be exact or slices leak."""
        a = CumulativeEventTimeWindows(max_size_ms=300, step_ms=100)
        # vectorized last window end must agree with the scalar path
        ses = np.array([100, 200, 300, 400, 600], dtype=np.int64)
        want = [a.window_ends_for_slice(int(s))[-1] for s in ses]
        got = a.last_window_ends(ses).tolist()
        assert got == want
        w = SliceSharedWindower(a, SumAggregate("v"), capacity=1024,
                                allowed_lateness=50)
        for step in range(10):
            t = step * 100
            w.process_batch(kb([1], [1.0], [t + 10]))
            w.on_watermark(t)
        w.on_watermark(10**6)
        assert w.table.num_used == 0

    def test_sliding_last_window_ends_vectorized_matches_scalar(self):
        for size, slide in [(300, 100), (500, 200), (1000, 300), (100, 100)]:
            a = SlidingEventTimeWindows.of(size, slide)
            ses = np.arange(1, 30) * a.slice_width
            want = [a.window_ends_for_slice(int(s))[-1] for s in ses]
            got = a.last_window_ends(ses).tolist()
            assert got == want, (size, slide)


ASSIGNERS = {
    "tumble": lambda: TumblingEventTimeWindows.of(100),
    "tumble_offset": lambda: TumblingEventTimeWindows.of(100, 30),
    "hop": lambda: SlidingEventTimeWindows.of(500, 100),
    "hop_uneven": lambda: SlidingEventTimeWindows.of(1000, 300),
    "hop_offset": lambda: SlidingEventTimeWindows.of(400, 200, 70),
    "cumulate": lambda: CumulativeEventTimeWindows(max_size_ms=300,
                                                   step_ms=100),
}


class TestOldestLiveSliceEnd:
    """The late-record filter's early-out as a threshold (what the
    batch sweep tests a batch's slice ends against, natively)."""

    @pytest.mark.parametrize("lateness", [0, 50, 250])
    @pytest.mark.parametrize("name", sorted(ASSIGNERS))
    def test_is_where_live_mask_starts_keeping(self, name, lateness):
        from flink_tpu.windowing.bookkeeping import SliceBookkeeper

        a = ASSIGNERS[name]()
        book = SliceBookkeeper(a, lateness)
        w = a.slice_width
        assert book.oldest_live_slice_end() < -(1 << 61)   # no watermark
        for wm in [-2345, -1, 0, 99, 100, 129, 130, 777, 1000, 5003]:
            book.watermark = wm
            first = book.oldest_live_slice_end()
            assert first == book.oldest_live_slice_end()    # kept per wm
            assert (first - a.offset) % w == 0
            ends = a.assign_slice_ends(
                np.arange(wm - 3000, wm + 3000, 7, dtype=np.int64))
            for se in np.unique(ends).tolist():
                kept = book.live_mask(
                    np.asarray([se], dtype=np.int64)) is None
                assert kept == (se >= first), (wm, se, first)


class TestLateBatchesKeepTheirPath:
    """A batch holding a late record is not swept: it takes the path
    that drops and counts late records, unchanged; the batches around it
    are swept (where the native index is there)."""

    @pytest.mark.parametrize("late", [0, 1, 3])
    def test_late_records_dropped_and_counted_as_before(self, late):
        from flink_tpu.native import slotmap_available
        from flink_tpu.observe import flight_recorder as flight

        w = SliceSharedWindower(SlidingEventTimeWindows.of(200, 100),
                                SumAggregate("v"), capacity=1024,
                                allowed_lateness=100)
        rec = flight.recorder()
        rec.clear()
        w.process_batch(kb([1, 2], [1.0, 1.0], [10, 120]))
        assert fired(w.on_watermark(450)) == {
            (1, -100, 100): 1.0, (1, 0, 200): 1.0,
            (2, 0, 200): 1.0, (2, 100, 300): 1.0}
        # slice (0, 100] left retention at 199 + 100, (100, 200] at 399;
        # (200, 300] is kept until 499
        ts = [460, 470, 480] + [50, 150, 199][:late]
        w.process_batch(kb([5] * len(ts), [1.0] * len(ts), ts))
        assert w.late_records_dropped == late
        w.process_batch(kb([5], [2.0], [250]))      # inside retention
        assert w.late_records_dropped == late
        # (window [100, 300) is past its own retention: not re-fired)
        assert fired(w.on_watermark(10 ** 6)) == {
            (5, 200, 400): 2.0,
            (5, 300, 500): 3.0, (5, 400, 600): 3.0}
        assert w.table.num_used == 0
        sweeps = rec.kind_totals().get("resolve.sweep",
                                       {"count": 0, "work": 0})
        rec.clear()
        if slotmap_available():
            assert sweeps["count"] == (3 if late == 0 else 2)
            assert sweeps["work"] == 3 + (3 if late == 0 else 0)
        else:
            assert sweeps["count"] == 0


class TestSessionLateness:
    def test_session_lateness_allows_new_session(self):
        from flink_tpu.windowing.sessions import SessionWindower

        w = SessionWindower(gap=50, agg=SumAggregate("v"), capacity=1024,
                            allowed_lateness=100)
        w.process_batch(kb([1], [1.0], [0]))
        w.on_watermark(200)
        # within lateness: accepted as a new session
        w.process_batch(kb([1], [2.0], [160]))
        assert w.late_records_dropped == 0
        # beyond lateness: dropped
        w.process_batch(kb([1], [4.0], [40]))
        assert w.late_records_dropped == 1
