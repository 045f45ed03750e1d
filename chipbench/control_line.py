"""A control through the benchmark's own ``correct``.

    python3 chipbench/control_line.py --workload <name> --seed <n> \
        --control program|reference [--which <control>] [--seconds <s>]

The cell's run as ``run.py`` makes it, the driver's ``run`` with its
``control`` set: "program" puts the family's control engine in the
program's place, "reference" judges the family's reference one
precision down in the served tokens' place. ``--which`` names the
control where the family's reference has several (its ``CONTROL``: the
afmoe and kimi_linear families read "operand", float8 operands into
every projection, unless told otherwise; afmoe's other is "window", the
window layers' ring kept at half its rows). The result line is the
cell's own and has to read ``"correct": false`` by ``served_gap_mean``
alone, every other check within its limit: the command exits 0 when it
does and 1 when the control passed. A benchmark run never comes here.
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
    ap.add_argument("--control", required=True,
                    choices=("program", "reference"))
    ap.add_argument("--which", default=None)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)

    from chipbench import harness, traffic

    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    mix = traffic.load(cell["traffic"])
    if args.which is not None:
        # the family's reference is an ordinary import: the module the
        # driver's own load of the family reads
        harness.load_config(bench, cell["config"])[2].reference.CONTROL = \
            args.which
    seconds = args.seconds if args.seconds is not None \
        else bench["run_seconds"]
    out = harness.load_driver(mix["driver"]).run(
        bench=bench, cell=cell, mix=mix, seed=args.seed,
        seconds=float(seconds), trace=False,
        t_process_start=T_PROCESS_START, control=args.control)
    print(json.dumps(out), flush=True)
    harness.print_checks(out)
    failed = [n for n, c in out["checks"].items()
              if c["value"] is None or c["value"] > c["limit"]]
    return 0 if failed == ["served_gap_mean"] else 1


if __name__ == "__main__":
    sys.exit(main())
