"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, once. It needs a TPU with the chips the cell asks
for and fails without a result where JAX finds none (there is no CPU
mode). The last line of standard output is the result object; everything
else goes to standard error or ``benchmark/out/``.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# ``python benchmark/run.py`` puts benchmark/, not the checkout, on the path
sys.path.insert(0, ROOT)

from benchmark.harness import lastline  # noqa: E402


def _watchdog(seconds):
    """A run that outlives its limit leaves, stacks on standard error,
    instead of holding the chip."""

    def expire():
        faulthandler.dump_traceback(file=sys.stderr)
        lastline.fail(f"not done after {seconds}s")

    t = threading.Timer(seconds, expire)
    t.daemon = True
    t.start()


def main(argv=None):
    out = lastline.claim_stdout()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _watchdog(1150.0)
    try:
        from benchmark.harness import manifest, runner

        man = manifest.manifest()
        cell, cfg, mix = runner.resolve(man, args.workload)
        device = runner.look_for_chips(int(cell["chips"]))
        result = runner.run_cell(
            man, cell, cfg, mix, device, args.seed, args.seconds, args.trace,
            T_PROCESS, os.path.join(HERE, "out"))
    except BaseException:  # noqa: BLE001 - every exit path ends here
        traceback.print_exc(file=sys.stderr)
        lastline.fail("the run broke; no result")
    lastline.finish(out, lastline.result_line(**result))


if __name__ == "__main__":
    main()
