"""Seconds the program's flight recorder summed over the window for the
span ``kinds`` (host clock, cumulative ``total_s`` after minus before the window),
``per`` window second (``window_seconds``, as a percentage) or per result
window the sink received (``result_windows``, in milliseconds)."""


def read(run, kinds, per):
    if not any(k in run.flight_s for k in kinds):
        return None
    seconds = sum(run.flight_s.get(k, 0.0) for k in kinds)
    if per == "window_seconds":
        return 100.0 * seconds / run.window_s
    if per == "result_windows":
        windows = run.sink.windows_written_between()
        return 1e3 * seconds / windows if windows else None
    raise ValueError(
        f"unknown denominator {per!r}; known: window_seconds, result_windows")
