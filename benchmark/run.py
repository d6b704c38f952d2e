#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process: set-up, warm-up, a window of ``--seconds``, then one JSON
object as the last line of standard output with the keys ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` in a
traced run).  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics.  Everything else worth reading is on
earlier lines or under ``benchmark/out/``.

It runs on the machine it is started on and never on the CPU: with no TPU,
or fewer chips than the cell asks for, it exits non-zero and prints no
result line.
"""
import time

T_PROCESS = time.monotonic()        # set-up is counted from here

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = p.parse_args(argv)

    from benchmark import harness as H

    try:
        cell = H.Cell(args.workload)
        driver = H.plugin("drivers", cell.config["kind"])
        line = driver.run(cell, args.seed, args.seconds, bool(args.trace),
                          T_PROCESS)
    except H.BenchmarkError as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 1
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
