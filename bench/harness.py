"""What every cell shares: finding a cell's files by the names in
``BENCHMARK.json``, the device checks, the compile counter, the profiler
window with the benchmark's own host annotations, and the result line.

Nothing here knows a configuration, a traffic mix or a metric by name:
those live in files of their own (``configs/``, ``traffic/``,
``metrics/``, ``rooflines/``) and a cell's kind (``kinds/<kind>.py``) is
named by its configuration file.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent


class BenchError(RuntimeError):
    """A cell that cannot run: it prints no result and exits nonzero."""


def load_module(path: pathlib.Path, name: str):
    """Import one file of the benchmark by path (metric names hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing {path.parent.name}/{path.name}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, resolved."""

    name: str
    chips: int
    config: dict          # the configuration file as it is run
    traffic: dict         # the traffic mix's data file
    end_to_end: list      # BENCHMARK.json entries that this cell reports
    per_layer: list
    peaks: dict

    @property
    def kind(self) -> str:
        return self.config["kind"]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: pathlib.Path = BENCH,
              bench_file: pathlib.Path | None = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` (beside the benchmark's
    directory ``bench``), with its data files read from ``bench``."""
    bench_file = bench_file or bench.parent / "BENCHMARK.json"
    if not bench_file.is_file():
        raise BenchError(f"no {bench_file.name} beside {bench.name}/")
    spec = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"{name}: unknown config {w['config']!r}")
    cfg_path = bench_file.parent / configs[w["config"]]["file"]
    if not cfg_path.is_file():
        raise BenchError(f"{name}: missing config file {cfg_path.name}")
    traffic_path = bench / "traffic" / f"{w['traffic']}.json"
    if not traffic_path.is_file():
        raise BenchError(f"{name}: no traffic mix traffic/{traffic_path.name}")
    per_layer = [m for m in spec["per_layer"] if _reports(m, name)]
    for m in per_layer:
        if not (bench / "metrics" / f"{m['name']}.py").is_file():
            raise BenchError(f"per-layer metric {m['name']!r} has no reader "
                             f"metrics/{m['name']}.py")
    return Cell(name=name, chips=int(w["chips"]),
                config=json.loads(cfg_path.read_text()),
                traffic=json.loads(traffic_path.read_text()),
                end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
                per_layer=per_layer,
                peaks=json.loads((bench / "peaks.json").read_text()))


def sub_seed(seed: int, *tags: int | str) -> int:
    """A 31-bit seed derived from the run's seed (any size) and tags, so
    every consumer of randomness gets its own stream."""
    import numpy as np
    words = [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF]
    for t in tags:
        words.append(t if isinstance(t, int) else
                     int.from_bytes(str(t).encode()[:4].ljust(4, b"\0"), "big"))
    return int(np.random.SeedSequence(words).generate_state(1)[0] >> 1)


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def devices(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: jax runs on {devs[0].platform}")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, jax sees "
                         f"{len(devs)}")
    return devs[:chips]


def peak_of(cell: Cell, device) -> dict:
    kind = device.device_kind
    if kind not in cell.peaks:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return cell.peaks[kind]


def memory_peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks))


class CompileCounter:
    """Counts executables that jax compiles or loads from its persistent
    cache, by phase; inside the measured window there should be none."""

    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.phase = "setup"
        self.compiles: dict[str, int] = {}
        self.cache_requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self.BACKEND:
            self.compiles[self.phase] = self.compiles.get(self.phase, 0) + 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def count(self, phase: str) -> int:
        return self.compiles.get(phase, 0)


# ---------------------------------------------------------------------------
# the profiler window and the benchmark's host annotations
# ---------------------------------------------------------------------------

class Tracing:
    """The traced run's profiler window.  ``ann(name, **args)`` marks what
    the host is doing (``bench.<name>``) on the profiler's own clock while
    the window is open, and costs a null context otherwise."""

    def __init__(self, on: bool, seconds: float):
        self.on = on
        self.seconds = seconds
        self.active = False
        self.dir = None
        self._window = None
        self._t0 = 0.0

    def start(self):
        if not self.on:
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(self.dir)
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()
        self._t0 = time.perf_counter()
        self.active = True

    def ann(self, name: str, **args):
        if not self.active:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}", **args)

    def poll(self):
        """Close the window once it has lasted its seconds."""
        if self.active and time.perf_counter() - self._t0 >= self.seconds:
            self.stop()

    def stop(self):
        if not self.active:
            return
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False

    def read(self, n_devices: int):
        """Reduce the recorded trace, then delete it."""
        self.stop()
        if self.dir is None:
            return None
        import tracefile
        try:
            files = sorted(pathlib.Path(self.dir).rglob("*.xplane.pb"))
            if not files:
                raise BenchError("the profiler wrote no trace")
            return tracefile.from_xplane(files[-1], n_devices)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None


# ---------------------------------------------------------------------------
# results
# ---------------------------------------------------------------------------

def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``q`` in 0-100) over every value; a missing
    sample is ``inf`` and counts as the slowest."""
    vals = sorted(values)
    if not vals:
        return math.inf
    k = max(1, math.ceil(q / 100.0 * len(vals)))
    return float(vals[k - 1])


def quote(x: float) -> float | str:
    return x if math.isfinite(x) else str(x)


def emit(*, correct: bool, attempted: int, failed: int, metrics: dict,
         device: dict, checks: dict, breakdown: dict | None = None):
    """Print the compared numbers as the last lines of standard error and
    the result as the last line of standard output."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": quote(v["value"]), "limit": v["limit"]}
                     for k, v in checks.items()}
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
