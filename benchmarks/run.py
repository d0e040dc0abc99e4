# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run              # everything
  PYTHONPATH=src python -m benchmarks.run table2 fig6  # subset
  PYTHONPATH=src python -m benchmarks.run --repeats 5 kmeans_build

First run trains + caches the pipeline under artifacts/lab/ (minutes on
one CPU core); later runs reuse it.

`--repeats N` re-runs each suite N times and rewrites its JSON record
with the MEDIAN of every wall-time metric — the noise-hardening the CI
bench-gate relies on. Every JSON-writing suite also gets stamped with
`repeats` and a machine `fingerprint` (cpu_count + arch);
check_regression.py refuses to compare medians taken on different
machines (it skips with a warning instead of false-redding).
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time
from typing import Dict, List

# the gate's metric detector — sharing it guarantees the medians taken
# here cover exactly the metrics check_regression.py will compare
from benchmarks.check_regression import _is_walltime
from repro.utils.compile_cache import enable_compile_cache


def machine_fingerprint() -> Dict:
    """What has to match for two wall-time records to be comparable.
    (`backend`/`kernel_mode` are recorded per suite already — this adds
    the host side: core count and CPU architecture.)"""
    return {"cpu_count": os.cpu_count(),
            "machine": platform.machine()}


def merge_records(records: List[Dict]) -> Dict:
    """Median-of-N merge: every top-level wall-time metric becomes the
    median across `records`; everything else (regime keys, config,
    derived ratios) comes from the last run."""
    merged = dict(records[-1])
    for key, value in records[-1].items():
        if not _is_walltime(key, value):
            continue
        vals = sorted(r[key] for r in records
                      if key in r and _is_walltime(key, r[key]))
        merged[key] = vals[len(vals) // 2]
    return merged


def _run_suite(name: str, fn, json_path, repeats: int):
    records = []
    for rep in range(repeats):
        t0 = time.monotonic()
        rows = fn()
        dt = time.monotonic() - t0
        if rep == repeats - 1:
            for r in rows:
                print(",".join(str(x) for x in r))
            print(f"{name},elapsed_s,{dt:.1f}")
        if json_path and os.path.exists(json_path):
            with open(json_path) as f:
                records.append(json.load(f))
    if json_path and records:
        merged = merge_records(records)
        merged["repeats"] = len(records)
        merged["fingerprint"] = machine_fingerprint()
        with open(json_path, "w") as f:
            json.dump(merged, f, indent=2)


def main(argv=None) -> None:
    import benchmarks.fig4_intraprogram as fig4
    import benchmarks.fig6_crossprogram as fig6
    import benchmarks.fig7_adaptation as fig7
    import benchmarks.framework_throughput as thr
    import benchmarks.kmeans_build as kmeans_build
    import benchmarks.set_attention_kernel as setattn
    import benchmarks.store_lifecycle as lifecycle
    import benchmarks.table1_embedding_params as t1
    import benchmarks.table2_bcsd as t2

    modules = {
        "table1": t1,
        "table2": t2,
        "fig4": fig4,
        "fig6": fig6,
        "fig7": fig7,
        "throughput": thr,
        "set_attn": setattn,
        "kmeans_build": kmeans_build,
        "store_lifecycle": lifecycle,
    }

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("suites", nargs="*",
                    help=f"subset to run (default: all of "
                         f"{', '.join(modules)})")
    ap.add_argument("--repeats", type=int, default=1,
                    help="run each suite N times; JSON records keep the "
                         "median of every wall-time metric")
    args = ap.parse_args(argv)
    if args.repeats < 1:
        ap.error("--repeats must be >= 1")

    unknown = [a for a in args.suites if a not in modules]
    if unknown:
        # a typo'd suite name must not silently run nothing — CI bench
        # steps depend on a non-zero exit to stay trustworthy
        print(f"unknown suite(s): {', '.join(unknown)}; "
              f"available: {', '.join(modules)}", file=sys.stderr)
        raise SystemExit(2)
    enable_compile_cache()
    want = list(args.suites) or list(modules)
    for name in want:
        mod = modules[name]
        _run_suite(name, mod.run, getattr(mod, "JSON_PATH", None),
                   args.repeats)


if __name__ == "__main__":
    main()
