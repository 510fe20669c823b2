"""The benchmark harness: finds a cell's files by name, checks the chip,
runs the cell's traffic kind, reads its per-layer metrics and prints the
result line.

A cell ``<name>`` is ``workloads/<name>.json`` (its configuration, traffic
kind, traffic parameters and chips); its configuration is
``configs/<config>.json``; its traffic kind is the module
``bench/traffic/<kind>.py``; each per-layer metric ``<metric>`` named in
``BENCHMARK.json`` is read by ``bench/metrics/<metric>.py``.  Adding a
cell, a configuration, a traffic kind or a metric adds files; no file here
changes.

A traffic kind's ``run(run: Run) -> Outcome`` builds the system from the
seed, warms every shape its window uses, measures inside ``run.window()``,
reads the device memory peak, frees the program's state and then checks
what the window produced against ``bench/reference.py``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
from pathlib import Path
from typing import Dict, Optional

import jax
from jax import monitoring

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
# A fixed path inside the checkout: JAX keys its cache entries on it.
CACHE_DIR = ROOT / ".jax_cache"
NO_CHIP_EXIT = 3


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str, data_dir: Path = BENCH_DIR) -> dict:
    """The cell's workload file, with its configuration under ``config``."""
    cell = load_json(Path(data_dir) / "workloads" / f"{name}.json")
    cell["name"] = name
    cell["config_name"] = cell["config"]
    cell["config"] = load_json(
        Path(data_dir) / "configs" / f"{cell['config']}.json"
    )
    return cell


def traffic_kind(kind: str):
    return importlib.import_module(f"bench.traffic.{kind}")


def metric_reader(name: str):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: dict, cell: str):
    """(end-to-end, per-layer) metric entries of BENCHMARK.json that
    ``cell`` reports."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if cell in m.get("workloads", [cell] if m["moves"] in names
                              else [])]
    return e2e, layer


def require_chips(n: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform} devices only")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found {len(devices)}")
    return devices


def configure_cache() -> None:
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileClock:
    """Backend compiles JAX reports while the process runs (a cache hit
    reports none)."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Outcome:
    """What a traffic kind's run measured and checked."""

    correct: bool
    attempted: int
    failed: int
    metrics: Dict[str, float]          # end-to-end values by name
    checks: Dict[str, tuple]           # name -> (value, limit)
    layer: dict                        # what the metric readers read
    memory_peak_bytes: int


class Run:
    """One run of one cell: what a traffic kind is given."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 t_start: float, devices):
        self.cell = cell
        self.config = cell["config"]
        self.params = cell["traffic"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.devices = devices
        self.clock = CompileClock()
        self.trace_dir: Optional[str] = None
        self.window_compiles: Optional[int] = None

    def span(self, name: str):
        """A host annotation on the profiler's clock, in traced runs."""
        if not self.trace:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(f"bench.{name}")

    @contextlib.contextmanager
    def window(self):
        """The measured window: profiled in traced runs, and its backend
        compiles counted in every run."""
        if self.trace:
            from jax._src.lib import _profiler

            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = _profiler.ProfileOptions()
            opts.python_tracer_level = 0  # no event per Python call
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        before = self.clock.count
        try:
            with self.span("window"):
                yield
        finally:
            self.window_compiles = self.clock.count - before
            if self.trace:
                jax.profiler.stop_trace()

    def memory_peak(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks))


def run_cell(spec: dict, cell: dict, *, seed: int, seconds: float,
             trace: bool, t_start: float, devices) -> dict:
    """Run ``cell`` once and return its result line as a dict."""
    run = Run(cell, seed, seconds, trace, t_start, devices)
    out = traffic_kind(cell["traffic"]["kind"]).run(run)
    e2e, layer = cell_metrics(spec, cell["name"])
    dev = devices[0]
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": out.memory_peak_bytes,
    }
    line = {"correct": bool(out.correct), "attempted": int(out.attempted),
            "failed": int(out.failed), "metrics": {}, "device": device}
    if trace:
        from bench import trace_reduce

        reduced = trace_reduce.reduce_trace(
            trace_reduce.find_xplane(run.trace_dir)
        )
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        mods = sorted(reduced["modules"].items(),
                      key=lambda kv: -kv[1]["seconds"])[:8]
        log(f"trace: busy {reduced['busy_s']:.3f} s of "
            f"{reduced['window_s']:.3f} s on {reduced['devices']} device(s);"
            f" modules {[(k, round(v['seconds'], 4), v['count']) for k, v in mods]}")
        ctx = dict(out.layer, trace=reduced, device_kind=dev.device_kind,
                   window_compiles=run.window_compiles)
        for m in layer:
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    else:
        for m in e2e:
            line["metrics"][m["name"]] = {"value": out.metrics[m["name"]],
                                          "unit": m["unit"]}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out.checks.items()}
    return line


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_json(ROOT / "BENCHMARK.json")
    if args.workload not in {w["name"] for w in spec["workloads"]}:
        log(f"no cell {args.workload!r} in BENCHMARK.json")
        return 2
    cell = find_cell(args.workload)
    try:
        devices = require_chips(int(cell["chips"]))
    except NoChip as e:
        log(str(e))
        return NO_CHIP_EXIT
    devices = devices[: int(cell["chips"])]
    configure_cache()
    dev = devices[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
        f"jax {jax.__version__}, cell {args.workload}, seed {args.seed}")
    line = run_cell(spec, cell, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), t_start=t_start, devices=devices)
    for name, c in line["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)
    return 0
