"""The controls of a serving cell's ``served_gap_mean``, read on the chip.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 \
        [--seconds 45]

For each seed, in one process, the cell's own set-up and window at its
own load twice (``drivers/serve.py``: ``serve_window``): with the
program as the cell runs it, and with the family's control engine, the
program's own path one precision down. The sampled requests of each go
through ``served_gaps``; those of the first also with the family's
reference one precision down judged in the served tokens' place. One
``CONTROL`` line a seed, and the per-token gaps of all three under
``chiprun_out/control/``. The limit lies between the largest reading of
the program and the smallest of the control (PERF.md section 2); a
benchmark run never comes here.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    args = ap.parse_args(argv)

    import numpy as np

    from chipbench import harness, traffic

    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    mix = traffic.load(cell["traffic"])
    serve = harness.load_driver(mix["driver"])
    out_dir = os.path.join(harness.ROOT, "chiprun_out", "control")
    os.makedirs(out_dir, exist_ok=True)
    pad_to = traffic.longest_request(mix)
    for seed in [int(s) for s in args.seeds.split(",")]:
        gaps = {}
        for name, engine, reference in (("served", False, False),
                                        ("reference_control", False, True),
                                        ("program_control", True, False)):
            if not reference:       # the reference's control: same tokens
                out, sample, (family, sizes, params) = serve.serve_window(
                    bench, cell, mix, seed, args.seconds, False,
                    time.perf_counter(), control=engine)
            gaps[name] = serve.served_gaps(family, sizes, params, sample,
                                           pad_to, control=reference)
            print("CONTROL " + json.dumps(
                {"workload": cell["name"], "seed": seed, "what": name,
                 "requests": len(sample), "tokens": len(gaps[name]),
                 "gap_mean": float(gaps[name].mean()),
                 "gap_widest": float(gaps[name].max()),
                 "not_the_best_share": float((gaps[name] > 0).mean()),
                 "limit": family.GAP_TOL, "window_correct": out["correct"],
                 "tokens_per_s": out["metrics"].get(
                     "serve_tokens_per_s", {}).get("value")}), flush=True)
        np.savez(os.path.join(out_dir, f"{cell['name']}-{seed}.npz"), **gaps)
    return 0


if __name__ == "__main__":
    sys.exit(main())
