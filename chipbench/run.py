"""The benchmark's one command.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Looks the cell up in BENCHMARK.json, loads ``configs/<config>.json``
(and through its ``"family"`` key ``families/<family>.py``, which owns
all that knows the architecture) and ``traffic/<traffic>.json`` by
name, runs the driver the traffic file names
(``drivers/<driver>.py``), and prints one JSON object as the last
line of standard output: with ``--trace 0`` the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics (one reader each under
``metrics/``) and the breakdown of the traced seconds; every number
that decided ``correct`` comes last in it, beside its limit, and again
as the last lines of standard error. It runs on the
machine it is started on and needs the chips the cell asks for: without
them it exits non-zero and prints no result.
"""
import time

T_PROCESS_START = time.perf_counter()

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness, traffic

    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    mix = traffic.load(cell["traffic"])
    seconds = args.seconds if args.seconds is not None \
        else bench["run_seconds"]
    driver = harness.load_driver(mix["driver"])
    out = driver.run(bench=bench, cell=cell, mix=mix, seed=args.seed,
                     seconds=float(seconds), trace=bool(args.trace),
                     t_process_start=T_PROCESS_START)
    print(json.dumps(out), flush=True)
    harness.print_checks(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
