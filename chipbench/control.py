"""The control of a cell's correctness check, run on the chip.

    python3 -m chipbench.control --workload <cell> --seeds 11,12,13

For each seed it builds the cell's inputs as a run does and lets the
control of the cell's driver (``control`` in ``chipbench/drivers/<driver>.py``)
produce what the timed path would: the plain reference, put in the
program's place and computed one precision below the configuration's.  It
prints the cell's numbers beside their limits, one JSON line per seed.  The
check is sound only if the control fails it.  Not part of a benchmark run;
the tests run it at CPU size.
"""
from __future__ import annotations

import argparse
import json
import sys

from chipbench import check


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one line each")
    args = ap.parse_args(argv)
    from chipbench import run

    root = run.ROOT
    _, cell, cfg, traffic = run.load_cell(root, args.workload)
    device = run.device_check(int(cell["chips"]))
    run.enable_compile_cache(root)
    limits = check.load_limits(root, args.workload)
    control = run.load_module(root, "drivers", traffic["driver"]).control
    for seed in (int(s) for s in args.seeds.split(",")):
        correct, checks = check.judge(control(cfg, traffic, seed), limits)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": correct, "checks": checks,
                          "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
