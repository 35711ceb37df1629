"""Profile the Q5 bench hot loop (run on the real backend).

Usage: python tools/profile_bench.py [records]
Prints top cumulative-time functions to stderr.
"""
import cProfile
import io
import pstats
import sys
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from flink_tpu.platform import enable_compilation_cache

enable_compilation_cache()

from bench import run


def main():
    total = int(sys.argv[1]) if len(sys.argv) > 1 else 4_000_000
    # warmup (compiles everything)
    run(total_records=1 << 21, num_auctions=100_000)
    prof = cProfile.Profile()
    prof.enable()
    stats = run(total_records=total)
    prof.disable()
    print(f"events_per_s={stats['events_per_s']:.0f} "
          f"fire={stats['fire_latency_ms']}", file=sys.stderr)
    s = io.StringIO()
    ps = pstats.Stats(prof, stream=s).sort_stats("cumulative")
    ps.print_stats(45)
    print(s.getvalue(), file=sys.stderr)


if __name__ == "__main__":
    main()
