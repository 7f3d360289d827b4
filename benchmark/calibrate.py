#!/usr/bin/env python3
"""Readings from which a cell's ``limits`` are set (PERF.md section 2), many
seeds in one process so that compilation is paid once:

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 --controls 3

For each seed the program's first steps against the float32 reference (the
lower reading); for the first ``--controls`` seeds also, in the program's
place, the reference at the stated precision (bfloat16: what a sound program
may read), the control (fp8) and each planted fault (the upper readings).
One JSON line per (seed, who); never a benchmark run, no window, no metric.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None):
    from benchmark import check, run
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--variants",
                    default="bfloat16,fp8,half_batch,state_unchanged")
    ap.add_argument("--leaves", action="store_true",
                    help="also print every leaf's norms, to try other numbers")
    args = ap.parse_args(argv)
    cell = run.Cell(args.workload, args.rehearse)
    import jax
    run.setup_cache(jax)
    if not args.rehearse and jax.local_devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 3
    driver = importlib.import_module(
        f"benchmark.drivers.{cell.traffic['driver']}")
    reference = importlib.import_module(
        f"benchmark.references.{cell.config['family']}")
    spans = run.Spans()
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        job = driver.Job(cell.config, cell.traffic, seed, spans)
        prog = job.first_steps()
        batches = job.check_batches()
        job.free()
        del job
        want = reference.first_steps(cell.config, seed, batches)
        rows = [("program", prog)]
        if n < args.controls:
            for variant in args.variants.split(","):
                kw = ({"precision": variant}
                      if variant in ("bfloat16", "fp8", "int8")
                      else {"fault": variant})
                rows.append((variant, reference.first_steps(
                    cell.config, seed, batches, **kw)))
        for who, got in rows:
            vals, where = check.numbers(got, want)
            line = {"cell": cell.name, "seed": seed, "who": who,
                    "numbers": vals, "where": where,
                    "loss": got["loss"], "ref_loss": want["loss"]}
            if args.leaves:
                line["readings"] = got
                line["reference"] = want
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
