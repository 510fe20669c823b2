"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

``reduce_trace(path, window="bench.window")`` reads the trace with
``jax.profiler.ProfileData`` and returns, over the interval of the host
annotation named ``window``:

  * ``busy_s``: the union of the intervals in which a device operation ran,
    averaged over the devices that ran any; ``window_s``: the window length;
  * ``modules``: device seconds and event count per XLA module name (one
    module event per program run), so a metric can take the time of the
    program it names;
  * ``device_ops``: the device operations that took most time, by the
    name before `` = `` in the op's HLO text; loops and conditionals,
    which hold other operations, are left out of this list;
  * ``idle_gaps``: idle device time within the window, summed by what the
    host was doing meanwhile: the innermost host event that spans the gap,
    else the one that overlaps it most; gaps shorter than SHORT_GAP_S are
    summed under one label.

On a TPU the device planes are ``/device:TPU:<i>``, with module events on
their "XLA Modules" line and operations on "XLA Ops".  On the CPU backend
(used to test this reduction) device operations are host events that carry
an ``hlo_op`` stat, and a module's time is the sum of its operations.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

import numpy as np

TOP = 10
SHORT_GAP_S = 1e-3  # shorter idle gaps are summed, not named
# HLO control flow: its events span the operations inside it
CONTAINER = re.compile(r"%?(while|cond|conditional|call)[.\d]")


def find_xplane(log_dir) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    paths = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _events(path):
    """(device_ops, module_events, host_events): lists of
    (plane, name, start_s, end_s) tuples."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, modules, host = [], [], []
    has_device = any(p.name.startswith("/device:") for p in data.planes)
    for plane in data.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                start = ev.start_ns * 1e-9
                row = (plane.name, ev.name, start,
                       start + ev.duration_ns * 1e-9)
                if on_device:
                    if "Module" in line.name:
                        modules.append(row)
                    elif "Ops" in line.name:
                        device_ops.append(row)
                    continue
                stats = {} if has_device else dict(ev.stats)
                if "hlo_op" in stats:
                    device_ops.append(row)
                    modules.append((stats.get("run_id"),
                                    str(stats.get("hlo_module")),
                                    row[2], row[3]))
                else:
                    host.append(row)
    if has_device and not device_ops:
        device_ops = list(modules)
    return device_ops, modules, host


def reduce_trace(path: str, window: str = "bench.window") -> Dict:
    device_ops, modules, host = _events(path)
    spans = [(a, b) for _p, name, a, b in host if name == window]
    if not spans:
        raise ValueError(f"no host annotation {window!r} in {path}")
    lo, hi = min(a for a, _ in spans), max(b for _, b in spans)

    busy_by_dev: Dict[str, List] = {}
    for plane, _name, a, b in device_ops:
        busy_by_dev.setdefault(plane, []).append((a, b))
    busy = {d: _union(_clip(iv, lo, hi)) for d, iv in busy_by_dev.items()}
    busy_s = [sum(b - a for a, b in iv) for iv in busy.values()]

    # on the CPU a module row is one of its operations, tagged with the run
    # it belongs to; on a TPU it is one run of the module
    per_module: Dict[str, List] = {}
    for tag, name, a, b in modules:
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        rec = per_module.setdefault(name, [0.0, set()])
        rec[0] += b - a
        rec[1].add((tag, a) if isinstance(tag, str) else tag)

    per_op: Dict[str, float] = {}
    for _plane, name, a, b in device_ops:
        a, b = max(a, lo), min(b, hi)
        name = name.split(" = ")[0]
        if b > a and not CONTAINER.match(name):
            per_op[name] = per_op.get(name, 0.0) + (b - a)

    # idle gaps of the busiest device, named by the host's overlap
    gaps: Dict[str, float] = {}
    if busy:
        dev = max(busy, key=lambda d: sum(b - a for a, b in busy[d]))
        edges = [lo] + [x for iv in busy[dev] for x in iv] + [hi]
        holes = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        named = [(n, a, b) for _p, n, a, b in host if n != window]
        names = [n for n, _a, _b in named]
        s = np.asarray([a for _n, a, _b in named], float)
        e = np.asarray([b for _n, _a, b in named], float)
        for a, b in holes:
            if b - a < SHORT_GAP_S:
                label = f"gaps under {SHORT_GAP_S * 1e3:g} ms"
            else:
                label = "host idle"
                inside = (s <= a) & (e >= b)
                ov = np.minimum(b, e) - np.maximum(a, s)
                if inside.any():  # the innermost host event around the gap
                    label = names[int(np.argmin(np.where(inside, e - s,
                                                         np.inf)))]
                elif (ov > 0).any():
                    label = names[int(np.argmax(ov))]
            gaps[label] = gaps.get(label, 0.0) + (b - a)

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:TOP]

    return {
        "window_s": hi - lo,
        "busy_s": sum(busy_s) / len(busy_s) if busy_s else 0.0,
        "devices": len(busy_s),
        "modules": {k: {"seconds": v[0], "count": len(v[1])}
                    for k, v in per_module.items()},
        "device_ops": top(per_op),
        "idle_gaps": top(gaps),
    }


def module_time(trace: Dict, name: str) -> Tuple[float, int]:
    """Device seconds and run count of the modules named ``name``: XLA's
    module name, before the ``(<fingerprint>)`` that the trace appends."""
    secs, runs = 0.0, 0
    for mod, rec in trace["modules"].items():
        if mod.split("(", 1)[0] == name:
            secs += rec["seconds"]
            runs += rec["count"]
    return secs, runs
