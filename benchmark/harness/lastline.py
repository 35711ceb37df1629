"""The one writer of a run's last line.

``claim_stdout()`` keeps a private duplicate of descriptor 1 for the
result and points descriptor 1 at standard error, so nothing else — print
sinks, C++ libraries, warnings, threads — can write into or after the
line. ``finish()`` writes it and leaves with ``os._exit``.
"""

import json
import os
import sys
import threading

_once = threading.Lock()

def claim_stdout():
    out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    return out


def result_line(correct, attempted, failed, metrics, device, compared,
                breakdown=None):
    """The result object, keys in the order the driver's contract shows
    them; ``compared`` (each number beside its limit) comes last."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed),
            "metrics": {name: {"value": m["value"], "unit": m["unit"]}
                        for name, m in metrics.items()},
            "device": dict(device)}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    return line


def compared_text(compared):
    """The numbers compared, one per line, for the end of standard error."""
    return "\n".join(
        f"compared {name}: value={c['value']!r} limit={c['limit']!r} "
        f"{'ok' if c['ok'] else 'FAILED'}" for name, c in compared.items())


def finish(out, line, code=0):
    """Write the last line and leave at once. Runs at most once."""
    if not _once.acquire(blocking=False):
        threading.Event().wait()  # another path is already leaving
    sys.stdout.flush()
    sys.stderr.write(compared_text(line.get("compared", {})) + "\n")
    sys.stderr.flush()
    out.write(json.dumps(line) + "\n")
    out.flush()
    os._exit(code)


def fail(why, code=1):
    """Leave without a result line: no accelerator, too few chips, a bare
    directory, an unknown name, or a run that broke."""
    sys.stdout.flush()
    sys.stderr.write(f"benchmark: {why}\n")
    sys.stderr.flush()
    os._exit(code)
