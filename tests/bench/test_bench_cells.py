"""Each benchmark cell's rehearsal path on the CPU, at tiny sizes.

A run's path is the one the chip runs: the harness finds the cell's files
by name, the traffic kind drives the program's timed entry points, and the
answers are compared with ``bench/reference.py``.  Only the look for a chip
is skipped.  The controls and the planted faults must turn ``correct``
false.
"""

import copy
import json
import sys
import time
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402
from repro.core import stream  # noqa: E402
from repro.core.stream import StreamScanner  # noqa: E402

SPEC = harness.load_json(harness.ROOT / "BENCHMARK.json")
SEED = 2**32 + 7  # more than 32 signed bits hold
TINY = {
    "loghub-hdfs-scan": {"buffer_bytes": 3 << 20, "chunk_bytes": 1 << 20},
}


def tiny(name: str, data_dir=harness.BENCH_DIR) -> dict:
    cell = harness.find_cell(name, data_dir)
    cell["config"].update(TINY.get(cell["config_name"], {}))
    return cell


def run_line(cell, *, spec=SPEC, trace=False, seconds=1.0) -> dict:
    return harness.run_cell(spec, cell, seed=SEED, seconds=seconds,
                            trace=trace, t_start=time.monotonic(),
                            devices=jax.devices())


def run_outcome(cell, **kw) -> harness.Outcome:
    run = harness.Run(cell, SEED, 1.0, False, time.monotonic(),
                      jax.devices())
    return harness.traffic_kind(cell["traffic"]["kind"]).run(run, **kw)


@pytest.mark.parametrize("name", ["scan-count", "scan-locate"])
def test_cell_rehearsal_equals_reference(name):
    line = run_line(tiny(name))
    e2e, _ = harness.cell_metrics(SPEC, name)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in e2e}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert all(c["value"] == 0 == c["limit"] for c in line["checks"].values())
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("name", ["scan-count", "scan-locate"])
def test_traced_rehearsal_reads_per_layer_metrics(name, monkeypatch):
    from bench import roofline

    # the CPU has no entry in peaks.json; a TPU's is used for the arithmetic
    monkeypatch.setattr(roofline, "hbm_bytes_per_s", lambda kind: 819e9)
    line = run_line(tiny(name), trace=True)
    _, layer = harness.cell_metrics(SPEC, name)
    assert line["correct"]
    assert set(line["metrics"]) == {m["name"] for m in layer}
    dev = line["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert line["breakdown"]["device_ops"]
    assert len(line["breakdown"]["idle_gaps"]) <= 10


def test_unknown_device_has_no_peaks():
    from bench import roofline

    with pytest.raises(KeyError):
        roofline.hbm_bytes_per_s("cpu")


def test_a_cell_from_a_new_workload_file_alone(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "workloads").mkdir()
    cfg = harness.load_json(harness.BENCH_DIR / "configs"
                            / "loghub-hdfs-scan.json")
    cfg.update(buffer_bytes=2 << 20, chunk_bytes=1 << 20,
               queries=["dfs.FSNamesystem", "ask"])
    (tmp_path / "configs" / "tiny-scan.json").write_text(json.dumps(cfg))
    (tmp_path / "workloads" / "tiny-scan-count.json").write_text(json.dumps(
        {"config": "tiny-scan", "chips": 1,
         "traffic": {"kind": "stream_scan", "mode": "count"}}))
    spec = copy.deepcopy(SPEC)
    spec["workloads"].append({"name": "tiny-scan-count",
                              "config": "tiny-scan", "traffic": "t",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "count_scan_gbps":
            m["workloads"].append("tiny-scan-count")
    line = run_line(harness.find_cell("tiny-scan-count", tmp_path),
                    spec=spec)
    assert line["correct"]
    assert set(line["metrics"]) == {"count_scan_gbps", "setup_s"}


# -- controls: the program's k = 1 path breaks the k = 0 guarantee ----------


@pytest.mark.parametrize("name", ["scan-count", "scan-locate"])
def test_control_is_not_correct(name):
    out = run_outcome(tiny(name), control=True)
    assert not out.correct
    assert out.checks["wrong_answers"][0] > 0


# -- faults planted under the timed path ------------------------------------


def _state_unchanged(monkeypatch):
    orig = stream._jitted_count_step

    def step_factory(*a, **kw):
        step = orig(*a, **kw)
        return lambda counts, *args, **kws: counts

    monkeypatch.setattr(stream, "_jitted_count_step", step_factory)


def _half_chunk(monkeypatch):
    orig = stream._jitted_count_step

    def step_factory(*a, **kw):
        step = orig(*a, **kw)

        def half(counts, window, length, prev_ov, plans, **kws):
            return step(counts, window, length // 2, prev_ov, plans, **kws)
        return half

    monkeypatch.setattr(stream, "_jitted_count_step", step_factory)


def _count_altered(monkeypatch):
    orig = StreamScanner.count_many

    def altered(self, *a, **kw):
        out = orig(self, *a, **kw).copy()
        out[0] += 1
        return out

    monkeypatch.setattr(StreamScanner, "count_many", altered)


def _mask_altered(monkeypatch):
    orig = StreamScanner._flush_mask

    def altered(self, *a, **kw):
        base, new_start, mask = orig(self, *a, **kw)
        mask = mask.copy()
        mask[0, -1] = ~mask[0, -1]
        return base, new_start, mask

    monkeypatch.setattr(StreamScanner, "_flush_mask", altered)


def _mask_half(monkeypatch):
    orig = StreamScanner._flush_mask

    def half(self, *a, **kw):
        base, new_start, mask = orig(self, *a, **kw)
        return base, new_start, mask[:, : mask.shape[1] // 2]

    monkeypatch.setattr(StreamScanner, "_flush_mask", half)


@pytest.mark.parametrize("name,fault", [
    ("scan-count", _state_unchanged),
    ("scan-count", _half_chunk),
    ("scan-count", _count_altered),
    ("scan-locate", _mask_altered),
    ("scan-locate", _mask_half),
])
def test_planted_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    line = run_line(tiny(name))
    assert not line["correct"]
    assert sum(c["value"] for c in line["checks"].values()) > 0
