"""The benchmark's generic part, driven by ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. Everything particular to
one of them lives in a file found by that name:

* ``bench/configs/<file>``         the configuration as it is run;
* ``bench/traffic/<traffic>.json`` the mix's parameters, its ``driver`` and
                                   the limits of its correctness check;
* ``bench/drivers/<driver>.py``    the adapter to one program entry point;
* ``bench/metrics/<metric>.py``    one reader per per-layer metric.

A driver exposes ``run(cell, seed, seconds, window) -> Outcome``. It opens
and closes ``window`` around the measured steps, reads the peak device
memory, frees the program's state and only then runs its reference. A
metric reader exposes ``read(ctx) -> float | None``; ``None`` leaves the
metric out of the line.
"""
from __future__ import annotations

import importlib.util
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / "bench_out" / "trace"


class NoChip(RuntimeError):
    pass


class WindowClosed(Exception):
    """Raised from inside the program's loop once the window has closed."""


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    manifest: Dict[str, Any]


@dataclass
class Outcome:
    metrics: Dict[str, float]                   # end-to-end, besides setup_s
    attempted: int
    failed: int
    checks: List[Tuple[str, float, float]]      # (name, value, limit)
    memory_peak_bytes: Optional[int]
    facts: Dict[str, Any] = field(default_factory=dict)  # for metric readers

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(v <= lim for _, v, lim in self.checks)


def load_manifest(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(name: str, root: Path = ROOT) -> Cell:
    manifest = load_manifest(root)
    work = {w["name"]: w for w in manifest["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    conf = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name, int(w["chips"]), config, traffic, manifest)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(cell: Cell):
    return load_module(BENCH / "drivers" / f"{cell.traffic['driver']}.py")


def cell_metrics(cell: Cell, kind: str) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` entries that this cell reports."""
    e2e = [m for m in cell.manifest["end_to_end"]
           if "workloads" not in m or cell.name in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in cell.manifest["per_layer"]
            if (cell.name in m["workloads"] if "workloads" in m else m["moves"] in moved)]


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"need {n} TPU chip(s); JAX sees {len(devs)} {devs[0].platform} device(s)")
    return devs[:n]


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Window:
    """The measured window on the host clock. With a trace directory, the
    profiler is started by ``arm`` (before the window, so that starting it
    costs the window nothing) and the window is a named host span in it."""

    def __init__(self, seconds: float, trace_dir: Optional[Path] = None):
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self._span = None
        self._armed = False

    def arm(self) -> None:
        if self.trace_dir is not None and not self._armed:
            import jax
            self._armed = True
            jax.profiler.start_trace(str(self.trace_dir))

    def open(self) -> float:
        self.arm()
        if self._armed:
            import jax
            self._span = jax.profiler.TraceAnnotation("bench_window")
            self._span.__enter__()
        self.t_open = time.perf_counter()
        return self.t_open

    def close(self) -> float:
        self.t_close = time.perf_counter()
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        return self.t_close

    def finish(self) -> None:
        """Stop the profiler (writes the trace); idempotent."""
        if self._armed:
            import jax
            self._armed = False
            jax.profiler.stop_trace()


@dataclass
class Context:
    """What a per-layer metric reader may use."""
    cell: Cell
    outcome: Outcome
    summary: Any                 # trace_reduce.TraceSummary or None
    device_kind: str
    chips: int


def read_per_layer(cell: Cell, ctx: Context) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in cell_metrics(cell, "per_layer"):
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
             devices) -> Dict[str, Any]:
    """Everything but the look for a chip: returns the result line's object."""
    import shutil
    trace_dir = None
    if trace:
        trace_dir = TRACE_DIR / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    window = Window(seconds, trace_dir)
    try:
        outcome = driver(cell).run(cell, seed, seconds, window)
    finally:
        window.finish()
    setup_s = window.t_open - t_start
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    units = {m["name"]: m["unit"] for m in cell.manifest["end_to_end"] + cell.manifest["per_layer"]}
    result: Dict[str, Any] = {"correct": outcome.correct, "attempted": outcome.attempted,
                              "failed": outcome.failed}
    if trace:
        from . import trace_reduce
        summary = trace_reduce.reduce_file(trace_reduce.find_trace(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = Context(cell, outcome, summary, dev.device_kind, len(devices))
        result["metrics"] = read_per_layer(cell, ctx)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["device"] = device
        result["breakdown"] = {"device_ops": [list(x) for x in summary.device_ops],
                               "idle_gaps": [list(x) for x in summary.idle_gaps]}
    else:
        values = dict(outcome.metrics, setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": units[m["name"]]}
                             for m in cell_metrics(cell, "end_to_end")}
        result["device"] = device
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in outcome.checks}
    for n, v, lim in outcome.checks:
        print(f"check {n}: {v!r} limit {lim!r} {'ok' if v <= lim else 'FAILED'}",
              file=sys.stderr, flush=True)
    return result


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = find_cell(args.workload)
    try:
        devices = require_chips(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start, devices)
    print(json.dumps(result), flush=True)
    return 0
