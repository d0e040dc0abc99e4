#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, on the chip.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 8 [--control 1]

Runs the cell once per seed in one process (set-up is long, and the
compiled programs are shared) and prints one JSON line per seed with
every compared number. `--control 1` puts the configuration's
control in the program's place (see `chipbench/driver.py`) and prints
the program's numbers of the same run beside the control's; the
control's set the upper reading of each limit, the program's over a
dozen seeds the lower one. The benchmark's own runs never run the
control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness
    harness.steady_heap()
    cell = harness.resolve(args.workload)
    info = harness.device_info(cell.chips)
    harness.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run_cell(args.workload, seed, args.seconds, False, info,
                             control=bool(args.control))
        r["diag"] = r.pop("diag", {})
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control, "correct": r["correct"],
                          "attempted": r["attempted"],
                          "metrics": r["metrics"],
                          "numbers": {k: v["value"]
                                      for k, v in r["checks"].items()},
                          "diag": r["diag"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
