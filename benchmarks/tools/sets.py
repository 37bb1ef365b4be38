#!/usr/bin/env python3
"""Sets of runs of one cell, as the driver makes them, and their spreads.

    python3 benchmarks/tools/sets.py --workload <cell> --seeds 11,12,13,14,15,16 \
        [--sets 2] [--seconds <run_seconds>] [--trace 0] [--out chiprun_out/sets.jsonl]

Each run is a process of its own (``benchmarks/run.py``; this parent never
touches JAX, so the chip is the child's). Every set uses the same seeds.
Prints one line per run and, per metric, each set's median and spread: the
distance between the first and third quartile (``statistics.quantiles(n=4)``)
as a share of the median. The bound of an end-to-end metric is about five
times the widest spread.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None):
    here = os.path.dirname(os.path.abspath(__file__))
    repo_root = os.path.dirname(os.path.dirname(here))
    with open(os.path.join(repo_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--seconds", type=float,
                        default=float(bench["run_seconds"]))
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    sets = []
    for k in range(args.sets):
        runs = []
        for seed in seeds:
            t = time.perf_counter()
            done = subprocess.run(
                bench["command"] + ["--workload", args.workload, "--seed",
                                    str(seed), "--seconds", str(args.seconds),
                                    "--trace", str(args.trace)],
                cwd=repo_root, capture_output=True, text=True)
            wall = time.perf_counter() - t
            if done.returncode != 0 or not done.stdout.strip():
                print(f"set {k} seed {seed}: rc={done.returncode}\n"
                      + done.stderr[-3000:], flush=True)
                return 1
            line = json.loads(done.stdout.strip().splitlines()[-1])
            line["set"], line["seed"], line["wall_s"] = k, seed, wall
            runs.append(line)
            values = {n: m["value"] for n, m in line["metrics"].items()}
            print(json.dumps({"set": k, "seed": seed, "wall_s": round(wall, 1),
                              "correct": line["correct"],
                              "attempted": line["attempted"], **values,
                              "peak": line["device"]["memory_peak_bytes"],
                              "busy_s": line["device"].get("busy_s"),
                              "checks": {n: c["value"] for n, c in
                                         line["checks"].items()}}),
                  flush=True)
            if not line["correct"]:
                print(done.stderr[-3000:], flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(line) + "\n")
        sets.append(runs)
    def reading(r, name):
        if name == "chip_claimed":      # the platform's part of setup_s
            return r["window"]["setup_phases"]["chip_claimed"]
        return r["metrics"].get(name, {}).get("value")

    for name in list(sets[0][0]["metrics"]) + ["chip_claimed"]:
        per_set = [[reading(r, name) for r in runs
                    if reading(r, name) is not None] for runs in sets]
        print(name, " | ".join(
            f"set {k}: median {statistics.median(v):.6g} spread "
            f"{100 * spread(v):.3f}%" if len(v) >= 2 else f"set {k}: {v}"
            for k, v in enumerate(per_set)), flush=True)
    return 0 if all(r["correct"] for runs in sets for r in runs) else 1


if __name__ == "__main__":
    sys.exit(main())
