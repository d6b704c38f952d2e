#!/usr/bin/env python3
"""The knee sweep of an open-loop cell: one run at each of a few offered
rates, to find once the highest rate the system sustains.  Part of no
check; run again by hand when the configuration or the engine changes:

    python benchmark/sweep.py --workload chat-open [--rates 2,3,4,5,6,8]
                              [--seconds 45] [--seed 1]

The knee is the highest rate at which no request fails and the wait does
not grow through the window: the median time from due to first token of
the requests due in the last third is not above the first third's by more
than the step between neighbouring rates shows as noise.  The cell's
traffic file then takes 0.8 of it as ``rate_per_s``, with this sweep's
lines as ``knee_sweep``.

Each rate is a process of its own (a process that has touched JAX holds
the chip), started from a parent that never imports JAX.
"""
import time

T_PROCESS = time.monotonic()

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def one(workload: str, rate: float, seconds: float, seed: int) -> int:
    from benchmark import harness as H
    from benchmark.drivers import serve

    cell = H.Cell(workload)
    line = json.loads(serve.run(cell, seed, seconds, False, T_PROCESS,
                                rate_per_s=rate))
    rec = H.load_json(os.path.join(
        H.OUT_DIR, f"records-{workload}-{seed}.json"))
    t0 = rec["t0"]
    thirds = [[], [], []]
    for r in rec["records"]:
        k = int((r["due_t"] - t0) // (seconds / 3.0))
        if 0 <= k < 3 and r["ok"]:
            thirds[k].append(1e3 * (r["first_t"] - r["due_t"]))
    print(json.dumps({
        "sweep": workload, "rate_per_s": rate, "seconds": seconds,
        "correct": line["correct"], "attempted": line["attempted"],
        "failed": line["failed"],
        "metrics": {k: v["value"] for k, v in line["metrics"].items()},
        "ttft_p50_ms_by_third": [H.percentile(t, 50) if t else None
                                 for t in thirds]}), flush=True)
    return 0


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", default="2,3,4,5,6,8")
    p.add_argument("--seconds", type=float, default=45.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--one", type=float, default=None, help=argparse.SUPPRESS)
    args = p.parse_args()
    if args.one is not None:
        return one(args.workload, args.one, args.seconds, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--workload",
             args.workload, "--one", str(rate), "--seconds",
             str(args.seconds), "--seed", str(args.seed)],
            capture_output=True, text=True)
        lines = [ln for ln in out.stdout.splitlines()
                 if ln.startswith('{"sweep"')]
        print(lines[-1] if lines else json.dumps(
            {"sweep": args.workload, "rate_per_s": rate,
             "error": out.stderr[-500:]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
