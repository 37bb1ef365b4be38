#!/usr/bin/env python3
"""python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json``. The last line of standard
output is the result's JSON object; everything else (set-up, counters,
memory, stages, the numbers compared beside their limits) goes to standard
error. Without the chips the cell asks for it prints no result and exits 2.
"""

import argparse
import json
import os
import sys
import time


def main(argv=None):
    t0 = time.perf_counter()
    here = os.path.dirname(os.path.abspath(__file__))
    repo_root = os.path.dirname(here)
    sys.path.insert(0, repo_root)
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmarks import harness

    try:
        line = harness.execute(args.workload, args.seed, args.seconds,
                               args.trace, repo_root, t0=t0)
    except harness.NoChip as e:
        print(f"benchmarks/run.py: no result: {e}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
