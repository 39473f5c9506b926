#!/usr/bin/env python3
"""The readings behind Falcon-H1-34B's cell's limits, in ONE
process on the chip:

    python benchmark/tests/read_limits_falcon.py \
        --workload falcon-h1-34b-s1-tune.doc32k-steps --seed 11 \
        --hows sound,float8_e4m3fn,one_group,no_carry [--trace 1]

The cell's own driver runs once (``run_tuning.main``, the timed window: what
is compared is the timed program's), then the check follows the first call
with the reference once for every name in ``--hows``: ``sound``, a control
precision (both operands of every matrix product rounded to that dtype), or
one of the planted faults (``reference/falcon_h1.py`` ``FAULTS``),
each over all of the call's steps (with ``--hows ""`` no check runs: a
window's time alone). Every reading goes through the harness's own ``compared`` / ``verdict``, one
JSON line each on stdout. Not run by the test suite."""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--hows", default="sound")
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    prepared = bench_run.prepare(argparse.Namespace(
        workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=args.trace, rehearse=args.rehearse))
    if isinstance(prepared, int):
        return prepared
    _, _, driver, ctx = prepared

    from benchmark.harness.result import verdict
    from benchmark.reference.falcon_h1 import FAULTS

    def emit(record):
        print(json.dumps(record, default=str), flush=True)

    with contextlib.redirect_stdout(sys.stderr):
        run = driver.run(ctx)
    emit({"reading": "program", "seed": args.seed,
          "end_to_end": run["end_to_end"], "summary": run["summary"],
          "counters": run["window"]["counters"],
          "memory_peak_bytes": run["window"]["memory_peak_bytes"],
          "trace": {k: v for k, v in (run.get("trace") or {}).items()
                    if k in ("busy_s", "window_s", "scope_s")}})
    for name in filter(None, args.hows.split(",")):
        how = ({} if name == "sound" else {"fault": name} if name in FAULTS
               else {"operand": name})
        t0 = time.perf_counter()
        with contextlib.redirect_stdout(sys.stderr):
            compared = run["check"](**how)
        emit({"reading": name, "seed": args.seed,
              "check_s": round(time.perf_counter() - t0, 1),
              "correct_under_committed_limits": verdict(compared),
              "compared": {k: v["value"] for k, v in compared.items()}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
