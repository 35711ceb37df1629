"""The control of a cell's comparison, at a size of your choosing.

    python3 benchmark/control.py --workload <name> --events <n> --seeds 1 2 3

For each seed: the reference put in the program's place with one stated
guarantee broken or in the nearest precision below the one stated
(``reference_rows(control=True)`` of the cell's job module), compared with
the plain reference as a run compares the sink's rows. Every number must
read beside its limit, and at least one over it: a control that passes
means the comparison cannot tell a wrong answer. NumPy only; needs no chip.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import manifest, runner  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--events", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    _, cfg, _ = runner.resolve(manifest.manifest(), args.workload)
    job, o = manifest.job(cfg["job"]), cfg["job_options"]
    passed = 0
    for seed in args.seeds:
        want = job.reference_rows(seed, args.events, o)
        verdict = job.compare(
            job.reference_rows(seed, args.events, o, control=True), want, o)
        over = any(c["value"] > c["limit"]
                   for c in verdict["numbers"].values())
        passed += not over
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "events": args.events, "control_fails": over,
                          **verdict}), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
