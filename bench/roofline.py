"""The yardstick behind the per-layer metrics: the least work a count does,
the chip's peaks, and the readings of spans and traces that several
metrics share.

A count over text of ``n`` bytes has to read each byte once, whatever
implementation runs it: ``least_bytes(n) = n``.  It does no arithmetic
worth counting against the chip's FLOP/s, so the bound is memory: a count
program's roofline share is ``least_bytes / device_seconds`` over the HBM
bandwidth of ``peaks.json``.  A device that the table does not hold is an
error.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Optional

from bench.trace_reduce import module_time

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def hbm_bytes_per_s(device_kind: str) -> float:
    with open(PEAKS) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device {device_kind!r} in {PEAKS}")
    return float(peaks[device_kind]["hbm_bytes_per_s"])


def least_bytes(text_bytes: int) -> int:
    """Bytes a count over ``text_bytes`` of text must move: one read each."""
    return int(text_bytes)


def count_roofline_pct(ctx: dict, module: str, text_bytes_per_run: float,
                       runs_expected: int) -> Optional[float]:
    """Share (%) of the HBM roofline reached by the runs of the XLA module
    named ``module`` in the traced window.  None when it did not run
    ``runs_expected`` times: the name then matches no program, or another
    one besides the count's."""
    seconds, runs = module_time(ctx["trace"], module)
    if runs != runs_expected or seconds <= 0:
        return None
    need = least_bytes(runs * text_bytes_per_run)
    return 100.0 * need / seconds / hbm_bytes_per_s(ctx["device_kind"])


def idle_share_pct(ctx: dict) -> Optional[float]:
    trace = ctx["trace"]
    if trace["window_s"] <= 0 or trace["devices"] == 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def span_mean_ms(ctx: dict, name: str) -> Optional[float]:
    """Mean duration (ms) of the program's recorder span ``name``."""
    rec = ctx.get("recorder")
    span = rec and rec["spans"].get(name)
    if not span or not span["count"]:
        return None
    return span["total_ms"] / span["count"]
