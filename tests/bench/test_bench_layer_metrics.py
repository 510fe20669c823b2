"""The stream host path's per-layer metric readers, on hand-built recorder
summaries: each reads its span or counter, and reads nothing (None, no
error) from a program that lacks them."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402

SPANS = {"wait": 140.0, "fetch": 110.0, "positions": 30.0}


def summary(spans=SPANS, counters=None, chunks=4):
    """A recorder summary as ``Recorder.summary()`` shapes it: ``chunks``
    spans of each name whose mean is ``spans[name]`` ms."""
    return {
        "metrics": {"counters": dict(counters or {}), "gauges": {},
                    "histograms": {}},
        "events": {},
        "spans": {name: {"count": chunks, "total_ms": ms * chunks}
                  for name, ms in spans.items()},
    }


@pytest.mark.parametrize("metric,span", [
    ("stream.wait_ms.locate", "wait"),
    ("stream.fetch_ms.locate", "fetch"),
    ("stream.positions_ms.locate", "positions"),
])
def test_span_reader_reads_the_mean_per_chunk(metric, span):
    read = harness.metric_reader(metric)
    assert read({"recorder": summary()}) == pytest.approx(SPANS[span])
    others = {k: v for k, v in SPANS.items() if k != span}
    assert read({"recorder": summary(spans=others)}) is None
    assert read({"recorder": None}) is None


def test_fetch_bytes_per_byte_reads_the_counters():
    """Eight mask rows per window byte, with the stream's last window
    nearly empty: the reading is the mask's rows, whatever the new bytes."""
    read = harness.metric_reader("stream.fetch_bytes_per_byte.locate")
    n, new = 10_485_760, 10_485_728
    counters = {"fetch_bytes": 4 * 8 * n, "put_bytes": 4 * n,
                "bytes_scanned": 3 * new + 100}
    got = read({"recorder": summary(counters=counters)})
    assert got == 8.0


@pytest.mark.parametrize("counters", [
    {},
    {"put_bytes": 100, "bytes_scanned": 100},
    {"fetch_bytes": 800, "bytes_scanned": 100},
])
def test_fetch_bytes_per_byte_is_none_without_its_counters(counters):
    read = harness.metric_reader("stream.fetch_bytes_per_byte.locate")
    assert read({"recorder": summary(counters=counters)}) is None
    assert read({"recorder": None}) is None


def test_new_readers_are_declared_for_the_locate_cell_only():
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    names = {"stream.wait_ms.locate", "stream.fetch_ms.locate",
             "stream.positions_ms.locate",
             "stream.fetch_bytes_per_byte.locate"}
    entries = [m for m in spec["per_layer"] if m["name"] in names]
    assert {m["name"] for m in entries} == names
    for m in entries:
        assert m["workloads"] == ["scan-locate"]
        assert m["moves"] == "locate_scan_gbps"
        assert m["layer"] == "stream host path"
        assert (harness.BENCH_DIR / "metrics" / f"{m['name']}.py").exists()
