"""Runs one cell of `BENCHMARK.json` once and assembles its result.

Everything that belongs to one configuration, traffic mix, cell or
metric is a file of its own, found by its name:

  chipbench/configs/<config>.json    sizes, suites, kernel choices
                                     (the `file` of the config entry)
  chipbench/traffic/<traffic>.json   the request loop (`driver`) and its
                                     parameters
  chipbench/traffic/<driver>.py      the request loop (`DRIVER`, a
                                     `chipbench.driver.Driver`)
  chipbench/limits/<cell>.json       the limit of each number `correct`
                                     compares
  chipbench/metrics/<metric>.py      `read(run)` -> value or None

So a cell, a mix, a kind of request loop or a metric is added as files
plus entries, and no file that is there changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parent.parent
HERE = ROOT / "chipbench"
CACHE_DIR = HERE / ".jax_cache"

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


def steady_heap() -> None:
    """Have glibc keep freed memory in the heap instead of returning it.

    The service allocates and frees host buffers of tens of MB on every
    request (the store's matrix downloaded, compaction's copies). With
    glibc's defaults each is a fresh `mmap` whose page faults cost a
    request tens of ms, and how much differs from process to process:
    the attach p95 of one seed read 153 ms in one run and 216 ms in the
    next (PERF.md, section 6). With no mmapped chunks and no trimming,
    buffers reuse warm heap pages and runs agree. Called first thing in
    the process, before any large allocation."""
    import ctypes
    libc = ctypes.CDLL("libc.so.6")
    libc.mallopt(-4, 0)              # M_MMAP_MAX: no mmapped chunks
    libc.mallopt(-1, 2**31 - 1)      # M_TRIM_THRESHOLD: never trim


def process_age() -> float:
    """Seconds since this process started (Linux `/proc`), so set-up
    includes the interpreter's start and every import."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    return (time.clock_gettime(time.CLOCK_BOOTTIME)
            - start_ticks / os.sysconf("SC_CLK_TCK"))


# ----------------------------------------------------------------- cells
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict[str, float]
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: Dict, cell: str, bench: Dict) -> bool:
    """Whether `metric` is reported in `cell`: its `workloads`, or, for a
    per-layer metric without one, every cell reporting what it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        moved = next(m for m in bench["end_to_end"]
                     if m["name"] == metric["moves"])
        return applies(moved, cell, bench)
    return True


def resolve(workload: str, root: Path = ROOT) -> Cell:
    """The cell named `workload` with its files, or KeyError /
    FileNotFoundError when an entry or a file is missing."""
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    metrics = {k: [m for m in bench[k] if applies(m, workload, bench)]
               for k in ("end_to_end", "per_layer")}
    for m in metrics["end_to_end"] + metrics["per_layer"]:
        reader_path(m["name"], root)          # every reader exists
    traffic = _load_json(root / "chipbench" / "traffic"
                         / f"{w['traffic']}.json")
    driver_path(traffic["driver"], root)
    return Cell(workload, w["chips"], _load_json(root / conf["file"]),
                traffic,
                _load_json(root / "chipbench" / "limits"
                           / f"{workload}.json"),
                metrics["end_to_end"], metrics["per_layer"])


def _module_path(kind: str, name: str, root: Path) -> Path:
    path = root / "chipbench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{name!r} has no module at {path}")
    return path


def reader_path(name: str, root: Path = ROOT) -> Path:
    return _module_path("metrics", name, root)


def driver_path(name: str, root: Path = ROOT) -> Path:
    return _module_path("traffic", name, root)


def _load(kind: str, name: str, path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod       # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def read_metric(name: str, run: "Run", root: Path = ROOT) -> Optional[float]:
    return _load("metrics", name, reader_path(name, root)).read(run)


def load_driver(name: str, root: Path = ROOT):
    """The request loop class of traffic kind `name`."""
    return _load("traffic", name, driver_path(name, root)).DRIVER


# ------------------------------------------------------------------ runs
@dataclasses.dataclass
class Request:
    i: int
    t0: float
    t1: float
    ok: bool
    spans: List


@dataclasses.dataclass
class Run:
    """What metric readers see: the window's requests on the host clock,
    the driver (for per-request work counts), the chip's peaks and, in a
    traced run, the reduced device trace."""
    cell: Cell
    driver: object
    requests: List[Request]
    window_s: float
    setup_s: float
    peaks: Dict[str, float]
    trace: Optional[object] = None

    @property
    def done(self) -> List[Request]:
        return [r for r in self.requests if r.ok]

    def latencies_ms(self) -> List[float]:
        return [(r.t1 - r.t0) * 1e3 for r in self.requests]

    def percentile(self, q: float) -> float:
        """q-th percentile (0-100) of every request's latency, in ms."""
        lat = sorted(self.latencies_ms())
        if len(lat) == 1:
            return lat[0]
        return statistics.quantiles(lat, n=100, method="inclusive")[
            int(q) - 1]

    def total(self, layer: str, key: str = "flops") -> float:
        """Work of `layer` that the window's completed requests needed."""
        return sum(self.driver.work(r.i)[layer][key] for r in self.done)

    def span_ms(self, *names: str) -> float:
        """Mean per request of the host time in spans `names`, in ms."""
        tot = sum(t1 - t0 for r in self.requests
                  for (n, t0, t1) in r.spans if n in names)
        return tot / len(self.requests) * 1e3

    def mfu(self, layer: str) -> float:
        """Needed FLOPs of `layer` per second over the chip's peak, %."""
        return self.total(layer) / self.window_s / self.peaks["flops"] * 100

    def roofline(self, layer: str, kernel: str) -> Optional[float]:
        """Least time the chip needs for `layer`'s work (the larger of
        FLOPs at peak and bytes at HBM bandwidth) over the device time of
        `kernel`'s events, %; None when the trace holds no such event."""
        if self.trace is None:
            return None
        secs = self.trace.kernel_seconds(kernel)
        if not secs:
            return None
        least = max(self.total(layer) / self.peaks["flops"],
                    self.total(layer, "bytes") / self.peaks["hbm_bytes_per_s"])
        return least / secs * 100


class CompileCounter:
    """Traces and backend compiles while armed."""

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, secs, **_):
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1


def window(driver, seconds: float) -> Tuple[List[Request], float]:
    """Closed loop: requests start until `seconds` have passed; the window
    ends when the last one returns. A request that raises is failed."""
    reqs: List[Request] = []
    start = time.perf_counter()
    i = 0
    while time.perf_counter() - start < seconds:
        driver.spans.log = []
        t0 = time.perf_counter()
        ok = True
        try:
            with driver.spans("request"):
                driver.request(i)
        except Exception as e:                 # counted, not fatal
            print(f"request {i} failed: {e!r}", file=sys.stderr)
            ok = False
        reqs.append(Request(i, t0, time.perf_counter(), ok,
                            driver.spans.log))
        i += 1
    return reqs, time.perf_counter() - start


def device_info(chips: int) -> Dict:
    """JAX's first device; SystemExit unless it is a TPU and at least
    `chips` are visible."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise SystemExit(f"chipbench: needs a TPU; JAX found "
                         f"{info['platform']!r}")
    if info["count"] < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips; JAX "
                         f"found {info['count']}")
    return info


def enable_cache():
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             info: Dict, control: bool = False, root: Path = ROOT,
             peak_table=None) -> Dict:
    """Set up, measure, check; returns the result line's object."""
    import jax
    from chipbench import driver as base
    from chipbench.peaks import peaks as lookup
    cell = resolve(workload, root)
    chip = (peak_table or lookup)(info["kind"])
    driver = load_driver(cell.traffic["driver"], root)(
        cell.config, cell.traffic, seed, control=control)
    jax.config.update("jax_default_matmul_precision", driver.precision)
    counter = CompileCounter()
    driver.setup()
    jax.effects_barrier()
    setup_s = process_age()
    counter.armed = True
    reduced = None
    if trace:
        from chipbench import trace as tr
        with tempfile.TemporaryDirectory() as tdir:
            jax.profiler.start_trace(tdir)
            reqs, window_s = window(driver, min(seconds,
                                                cell.traffic["trace_seconds"]))
            jax.profiler.stop_trace()
            counter.armed = False
            reduced = tr.reduce(tr.find_xplane(tdir), base.SPANS)
    else:
        reqs, window_s = window(driver, seconds)
    counter.armed = False
    stats = jax.devices()[0].memory_stats() or {}
    info = dict(info, memory_peak_bytes=int(stats.get("peak_bytes_in_use",
                                                      0)))
    run = Run(cell, driver, reqs, window_s, setup_s, chip, reduced)
    names = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in names:
        v = read_metric(m["name"], run, root)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {"attempted": len(reqs), "failed": sum(not r.ok for r in reqs),
           "metrics": metrics}
    if trace:
        info.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        out["breakdown"] = reduced.breakdown()
    out["device"] = info
    driver.release()
    numbers = driver.check()
    numbers["window_compiles"] = counter.count
    numbers["failed_requests"] = out["failed"]
    out["checks"] = compare(numbers, cell.limits)
    out["correct"] = all(c["value"] <= c["limit"]
                         for c in out["checks"].values())
    out["diag"] = driver.diag
    return out


def compare(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """{name: {value, limit}} for every number; each needs a limit."""
    missing = set(numbers) ^ set(limits)
    if missing:
        raise KeyError(f"numbers and limits differ: {sorted(missing)}")
    return {n: {"value": numbers[n], "limit": limits[n]} for n in numbers}


def emit(result: Dict) -> None:
    """Compared numbers as the last lines of stderr, then the result as
    the last line of stdout with `checks` as its last key."""
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    keys = ("correct", "attempted", "failed", "metrics", "device",
            "breakdown", "checks")
    print(json.dumps({k: result[k] for k in keys if k in result}),
          flush=True)
