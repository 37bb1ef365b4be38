#!/usr/bin/env python3
"""Readings for the limits of one cell, several seeds in one process.

    python3 benchmarks/tools/readings.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2] [--fault half_fit] [--jobs 3] \
        [--out chiprun_out/readings.jsonl]

For each seed: the table from the seed at the cell's own size, ``--jobs``
jobs through the cell's timed path (after one warm-up job), every compared
number against the float64 reference (the lower readings). For a control
seed also the reference computed in bfloat16 (``refmath.round_bf16`` after
every stored intermediate) put in the program's place (the upper readings).
With ``--fault <name>`` the program runs with that fault of
``tools/faults.py`` planted, and the readings are the fault's. One JSON line
per seed. Needs the chip, like a run. Not run by the
benchmark's own runs.
"""

import argparse
import gc
import json
import os
import sys
import time


def main(argv=None):
    here = os.path.dirname(os.path.abspath(__file__))
    repo_root = os.path.dirname(os.path.dirname(here))
    sys.path.insert(0, repo_root)
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--fault", default=None)
    parser.add_argument("--jobs", type=int, default=3)
    parser.add_argument("--rows", type=int, default=None)
    parser.add_argument("--cpu-ok", action="store_true")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    from benchmarks import harness, refmath

    spec = harness.load_cell(args.workload, repo_root)
    cfg, cfg_mod, traffic = spec["cfg"], spec["cfg_mod"], spec["traffic"]
    job_mod = spec["job_mod"]
    import jax

    try:
        devices, device = harness.device_info(1, not args.cpu_ok)
    except harness.NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    import sparkdq4ml_tpu as dq
    from sparkdq4ml_tpu.utils.profiling import counters

    master = "tpu[*]" if device["platform"] == "tpu" else "local[*]"
    spark = (dq.TpuSession.builder().app_name("readings").master(master)
             .get_or_create())
    control = {int(s) for s in args.control_seeds.split(",") if s}
    out = open(args.out, "a") if args.out else None
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        table = cfg_mod.make_table(cfg, seed, args.rows)
        job = job_mod.Job(spark, cfg, cfg_mod, traffic["params"], table)
        if args.fault:
            from benchmarks.tools import faults

            faults.FAULTS[args.workload][args.fault](job)
        job.run(harness.Stages(False))
        before = counters.snapshot()
        times, results = [], []
        for _ in range(args.jobs):
            t = time.perf_counter()
            results.append(job.run(harness.Stages(False)))
            times.append(time.perf_counter() - t)
            gc.collect()
        per_job = harness.counter_delta(counters.snapshot(), before)
        in_use, peak = harness.memory_stats(devices)
        host = jax.device_get(table)
        getattr(job, "_undo", lambda: None)()
        job.close()
        del table, job
        gc.collect()
        t = time.perf_counter()
        want = job_mod.reference(cfg, cfg_mod, traffic["params"], host)
        reference_s = time.perf_counter() - t
        lower = {}
        for got in results:
            for name, gap in job_mod.compare(got, want).items():
                lower[name] = max(lower.get(name, 0.0), gap)
        line = {"workload": args.workload, "seed": seed, "device": device,
                "fault": args.fault,
                "job_s": times, "reference_s": reference_s,
                "counters_over_jobs": per_job, "bytes_in_use": in_use,
                "peak_bytes": peak, "program": lower}
        if seed in control:
            t = time.perf_counter()
            low = job_mod.reference(cfg, cfg_mod, traffic["params"], host,
                                    q=refmath.round_bf16)
            line["control_s"] = time.perf_counter() - t
            line["control_bf16"] = job_mod.compare(low, want)
        del host, want
        text = json.dumps(line, default=float)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
    spark.stop()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
