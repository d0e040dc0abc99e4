#!/usr/bin/env python3
"""Run one cell of the SemanticBBV benchmark once, on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout. The cell's configuration, traffic mix,
limits and metric readers are found through `BENCHMARK.json` (see
`chipbench/harness.py`). The run has glibc keep freed memory in its heap
(`harness.steady_heap`), refuses anything but a TPU with enough chips
before any work, keeps JAX's compilation cache in
`chipbench/.jax_cache`, sets up (weights and traffic from the seed,
every shape warmed), measures a closed-loop window of whole requests,
then checks a seeded sample of the window's answers against the plain
reference. The last stderr lines give each compared number beside its
limit; the last stdout line is the result as one JSON object. With
`--trace 1` the window is the traffic's `trace_seconds` under the
profiler and the metrics are the per-layer ones.
"""
from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness
    harness.steady_heap()
    cell = harness.resolve(args.workload)
    info = harness.device_info(cell.chips)
    harness.enable_cache()
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), info)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
