"""One benchmark run with its set-up laid out program by program:

    chiprun -- python tools/setup_report.py --workload <cell> --seed <n> \\
        --seconds 20 [--trace 1] [--rehearse]

The arguments are ``benchmark/run.py``'s, and the run is its ``main`` in this
process (the result line is printed as it prints it). Afterwards the compile
log (``obs.compiles()``, docs/OBSERVABILITY.md) is reduced to what a set-up PR
is read on: every program that closed before the window opened and took half a
second or more, by ``owner`` or ``fun_name``, with its trace, lowering and
backend (compile-or-retrieve) seconds and what the persistent cache did; the
rest in one row; the sums; and the remainder of ``setup_s`` that no program
accounts for (imports, backend start, the cache's key hashing, the weights,
the checked steps on the device). The same as one JSON object goes to
``chiprun_out/setup/<cell>.<seed>.json``.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys

import _bootstrap  # noqa: F401  (puts the repo root on sys.path)

from benchmark import run   # first: set-up is counted from its import

ROW_SECONDS = 0.5


PHASES = {"trace_s": "trace_seconds", "lower_s": "lower_seconds",
          "backend_s": "backend_seconds"}


def _cache(entry):
    if entry["cache_served"]:
        return "served"
    if entry["cache_written"]:
        return "written"
    return "asked, compiled, NOT written" if entry["cache_asked"] \
        else "not asked"


def reduce(log, setup_s, window_start):
    """The report as a dict, from the log and the run's two times."""
    before = [e for e in log if e["end"] < window_start]
    seconds = lambda e: sum(e[key] for key in PHASES.values())
    rows = [{"program": e["owner"] or e["fun_name"], "fun_name": e["fun_name"],
             **{short: e[key] for short, key in PHASES.items()},
             "retrieval_s": e["retrieval_seconds"], "cache": _cache(e),
             "ended_at_s": setup_s - (window_start - e["end"])}
            for e in before if seconds(e) >= ROW_SECONDS]
    total = {short: sum(e[key] for e in before)
             for short, key in PHASES.items()}
    asked, served, written = (sum(e[flag] for e in before) for flag in
                              ("cache_asked", "cache_served", "cache_written"))
    return {"setup_s": setup_s, "programs": len(before), "rows": rows,
            "other_programs_s": sum(seconds(e) for e in before
                                    if seconds(e) < ROW_SECONDS),
            **total, "remainder_s": setup_s - sum(total.values()),
            "cache_requests": asked, "cache_hits": served,
            "cache_writes": written,
            "compiled_and_not_written": asked - served - written}


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        code = run.main(argv)
    sys.stdout.write(printed.getvalue())
    if code:
        return code
    from deeplearning4j_tpu import obs
    out = json.loads(printed.getvalue().strip().splitlines()[-1])
    setup_s = out["run"]["setup_s"]
    report = reduce(obs.compiles(), setup_s, run._T0 + setup_s)
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    args, _ = ap.parse_known_args(argv)
    report.update(workload=args.workload, seed=args.seed,
                  metrics=out["metrics"],
                  per_layer_untraced=out["run"].get("per_layer_untraced"))
    for r in report["rows"]:
        print("  {program:28s} trace {trace_s:7.2f}  lower {lower_s:7.2f}  "
              "backend {backend_s:7.2f}  cache {cache}  (at {ended_at_s:.1f} s)"
              .format(**r), file=sys.stderr)
    print("  {programs} programs: trace {trace_s:.2f} + lower {lower_s:.2f} + "
          "backend {backend_s:.2f}; remainder {remainder_s:.2f} of setup_s "
          "{setup_s:.2f}; cache asked {cache_requests}, served {cache_hits}, "
          "written {cache_writes}".format(**report), file=sys.stderr)
    path = os.path.join(run.ROOT, "chiprun_out", "setup",
                        f"{report['workload']}.{report['seed']}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
