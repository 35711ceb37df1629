"""Bytes a windowed aggregation's device work needs, counted from the
traffic and never from the program: whatever kernel does the work, the
same traffic needs the same bytes. The roofline share divides the least
time these bytes could take by ALL the time the device was busy.
"""


def window_state_bytes(events, value_bytes_per_event, leaf_bytes,
                       fired_cells, emitted_rows, row_bytes):
    """Least bytes through device memory for ``events`` ingested and the
    windows fired beside them.

    Per event:
    - 4 bytes in: the int32 slot index of its (key, window-slice) cell;
    - ``value_bytes_per_event`` in: each non-constant value column at its
      width (a COUNT folds the constant 1 and reads none);
    - for each accumulator leaf, one read and one write of the cell it
      folds into (``2 * leaf_bytes``).
    Per fired window:
    - one read of every live (key, slice) accumulator it covers
      (``fired_cells`` over all fires, times each leaf's bytes);
    - one write of every emitted row (``emitted_rows * row_bytes``).
    Retiring a slice (resetting its cells) and slot-index traffic of the
    fire are left out: the count is a floor, so the share cannot be
    flattered by it.
    """
    leaves = sum(leaf_bytes)
    per_event = 4 + value_bytes_per_event + 2 * leaves
    return (events * per_event
            + fired_cells * leaves
            + emitted_rows * row_bytes)


def roofline_share(bytes_needed, peak_bytes_per_s, busy_s):
    """Least time over busy time, as a percentage; ``None`` where nothing
    ran (a share of a roofline is never reported as 0)."""
    if busy_s <= 0 or bytes_needed <= 0:
        return None
    return 100.0 * (bytes_needed / peak_bytes_per_s) / busy_s
