"""Telemetry-plane acceptance (DESIGN.md §13): the flight recorder tells the
TRUTH about a chaotic scan, and costs nothing when off.

  * a fault-seeded stealing sharded scan exports a schema-valid
    Chrome/Perfetto trace: spans properly nested per lane, ONE retry event
    per injected recoverable fault, every steal/shed carrying its exact
    beta-aligned byte range, and range_done events that exactly tile the
    input — verified against the same clean oracles test_fault_injection
    uses (extend the sweep with FAULT_SEEDS=0,1,2,... like the chaos job);
  * partial-mode coverage: merged range_done ranges == the PartialScanResult
    covered complement, event-for-struct;
  * the disabled recorder is inert (shared NULL_SPAN, no buffers) while
    events still reach log sinks;
  * straggler flags and the auto-chunk probe route through the recorder;
  * benchmarks/validate_trace.py accepts real exports and rejects each
    schema violation class it claims to catch.
"""

import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from benchmarks.validate_trace import TraceSchemaError, validate_trace  # noqa: E402
from test_fault_injection import FAULT_SEEDS, _corpus  # noqa: E402

from repro.core.shard_stream import PartialScanResult, ShardedStreamScanner
from repro.core.stream import StreamScanner
from repro.dist.fault_injection import FaultPlan, FaultyRangeSource
from repro.dist.fault_tolerance import (
    BackoffPolicy,
    FatalScanError,
    StepWatchdog,
    run_with_retries,
)
from repro.dist.sharding import merge_ranges
from repro.obs import NULL, NULL_SPAN, Metrics, Recorder


def _tiles(ranges, total):
    """True iff the (start, stop) ranges exactly tile [0, total)."""
    ranges = sorted((int(s), int(e)) for s, e in ranges)
    if not ranges or ranges[0][0] != 0 or ranges[-1][1] != total:
        return False
    return all(a[1] == b[0] for a, b in zip(ranges, ranges[1:]))


# -- the acceptance property: traced chaos scan ----------------------------


@pytest.mark.parametrize("seed", FAULT_SEEDS)
def test_traced_faulty_stealing_scan(rng, seed, tmp_path):
    """Fault-seeded stealing scan with the recorder on: results stay
    bit-identical, the trace is schema-valid (per-lane nesting included),
    retries map 1:1 to injected aborting faults, steal/shed ranges are
    beta-aligned, and range_done events exactly tile the input."""
    text, plans = _corpus(rng)
    want = StreamScanner(plans, 4096).count_many(text)

    plan = FaultPlan(
        seed, read_error_rate=0.08, crash_rate=0.12, attempts_per_fault=1,
    )
    rec = Recorder(enabled=True, fence=False)
    sc = ShardedStreamScanner(
        plans, 4, 4096, max_retries=16, fault_plan=plan,
        steal=True, steal_workers=3, min_steal_bytes=1024,
        backoff=BackoffPolicy(base_s=0.0, jitter=0.0),
        recorder=rec,
    )
    np.testing.assert_array_equal(
        sc.count_many(FaultyRangeSource(text, plan, piece_bytes=8192)), want
    )

    # one retry per injected fault: every fault here aborts its attempt
    # (read errors + crashes, no latency), and the budget never exhausts
    faults = rec.events_named("fault")
    retries = rec.events_named("retry")
    assert len(faults) == len(retries)
    assert sum(plan.counts_by_action().values()) == len(faults)
    assert all(isinstance(e["attempt"], int) for e in retries)

    # every steal/shed carries its exact beta-aligned byte range
    moves = rec.events_named("steal") + rec.events_named("shed")
    assert len(moves) == len(sc.steal_events)
    for ev in moves:
        assert 0 <= ev["start"] < ev["stop"] <= len(text)
        assert ev["start"] % 8 == 0
    for ev in rec.events_named("steal"):
        assert ev["thief"] is not None

    # retired ranges exactly tile the input despite repartitioning
    done = [(e["start"], e["stop"]) for e in rec.events_named("range_done")]
    assert _tiles(done, len(text))

    # the export passes the same validator CI runs (incl. span nesting)
    trace = rec.trace_json()
    assert validate_trace(trace) == len(trace["traceEvents"])
    names = {ev["name"] for ev in trace["traceEvents"] if ev["ph"] == "X"}
    assert {"host_prep", "device_put", "dispatch", "scan_range"} <= names
    out = tmp_path / "trace.json"
    rec.export_trace(out)
    assert validate_trace(json.loads(out.read_text())) > 0


def test_partial_scan_range_done_matches_covered(rng):
    """Permanent crashes + on_exhausted='partial': the union of range_done
    events IS the covered complement the PartialScanResult reports."""
    text, plans = _corpus(rng, n=64_000)
    plan = FaultPlan(1, crash_rate=0.5, attempts_per_fault=None)
    rec = Recorder(enabled=True, fence=False)
    sc = ShardedStreamScanner(
        plans, 8, 2048, max_retries=1, fault_plan=plan,
        on_exhausted="partial", steal=True, steal_workers=3,
        min_steal_bytes=512, recorder=rec,
    )
    res = sc.count_many(text)
    assert isinstance(res, PartialScanResult)
    assert not res.complete
    done = [(e["start"], e["stop"]) for e in rec.events_named("range_done")]
    assert merge_ranges(done) == res.covered
    lost = [(e["start"], e["stop"]) for e in rec.events_named("range_lost")]
    assert lost, "exhausted ranges must be recorded as range_lost events"
    assert _tiles(done + list(res.missing), len(text))
    validate_trace(rec.trace_json())


# -- disabled path ----------------------------------------------------------


def test_disabled_recorder_is_inert_but_sinks_still_fire(rng):
    captured = []
    rec = Recorder(
        enabled=False, fence=False,
        sinks=(lambda name, args: captured.append((name, dict(args))),),
    )
    assert rec.span("anything", lane="x", a=1) is NULL_SPAN
    obj = object()
    assert NULL_SPAN.fence(obj) is obj  # no sync, identity passthrough
    NULL_SPAN.set(a=1)  # no-op, no error

    text, plans = _corpus(rng, n=20_000)
    want = StreamScanner(plans, 2048).count_many(text)
    got = StreamScanner(plans, 2048, recorder=rec).count_many(text)
    np.testing.assert_array_equal(got, want)
    assert rec.trace_json()["traceEvents"] == []  # nothing buffered
    assert rec.events_named("fault") == []

    rec.event("straggler", step=3, duration_s=0.5)
    assert captured == [("straggler", {"step": 3, "duration_s": 0.5})]
    assert rec.events_named("straggler") == []  # sink-only when disabled


# -- satellite routing: stragglers + auto-chunk probe ----------------------


def test_straggler_flag_routes_through_recorder(rng):
    text, plans = _corpus(rng, n=40_000)
    rec = Recorder(enabled=True, fence=False)
    flagged = []

    def slow_source():
        for i in range(0, len(text), 4096):
            if i == 6 * 4096:
                time.sleep(0.05)  # one stalled read, well past 3x median
            yield text[i : i + 4096]

    sc = StreamScanner(
        plans, 4096, recorder=rec,
        watchdog=StepWatchdog(factor=3.0, policy="log", min_history=3),
        on_straggler=flagged.append,
    )
    want = StreamScanner(plans, 4096).count_many(text)
    np.testing.assert_array_equal(sc.count_many(slow_source()), want)

    evs = rec.events_named("straggler")
    assert evs and len(evs) == len(flagged)  # recorder and callback agree
    for ev, cb in zip(evs, flagged):
        assert ev["step"] == cb.step
        assert ev["duration_s"] > 0 and ev["factor"] >= 3.0


def test_auto_chunk_probe_routes_through_recorder():
    from repro.core import engine

    rec = Recorder(enabled=True, fence=False)
    sc = StreamScanner(
        engine.compile_patterns([b"abab"]), "auto", recorder=rec
    )
    (ev,) = rec.events_named("auto_chunk")
    assert ev["chunk_bytes"] == sc.chunk_bytes > 0
    assert ev["dispatch_overhead_us"] > 0


# -- retry-loop + remote-reader events -------------------------------------


def test_run_with_retries_emits_structured_events():
    rec = Recorder(enabled=True, fence=False)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise IOError("transient")
        return "ok"

    assert run_with_retries(
        flaky, retries=5, recorder=rec, label="shard3",
        backoff=BackoffPolicy(base_s=0.0, jitter=0.0),
    ) == "ok"
    evs = rec.events_named("retry")
    assert [e["attempt"] for e in evs] == [0, 1]
    assert all(e["task"] == "shard3" for e in evs)

    with pytest.raises(IOError):
        run_with_retries(
            lambda: (_ for _ in ()).throw(IOError("always")),
            retries=1, recorder=rec, label="doomed",
            backoff=BackoffPolicy(base_s=0.0, jitter=0.0),
        )
    (ex,) = rec.events_named("retry_exhausted")
    assert ex["task"] == "doomed" and ex["attempt"] == 1

    with pytest.raises(FatalScanError):
        run_with_retries(
            lambda: (_ for _ in ()).throw(FatalScanError("auth")),
            retries=5, recorder=rec, label="fatal",
        )
    (ft,) = rec.events_named("retry_fatal")
    assert ft["task"] == "fatal" and ft["attempt"] == 0
    assert len(rec.events_named("retry")) == 3  # exhausted run retried once


def test_remote_reader_records_part_spans_and_retries():
    from repro.core.remote_source import FakeObjectStore

    data = bytes(range(256)) * 64  # 16 KiB
    plan = FaultPlan(5, read_error_rate=0.4, attempts_per_fault=1)
    rec = Recorder(enabled=True, fence=False)
    store = FakeObjectStore(data, plan=plan)
    reader = store.reader(
        part_bytes=1024, prefetch=2, retries=4,
        backoff=BackoffPolicy(base_s=0.0, jitter=0.0), sleep=lambda s: None,
        recorder=rec,
    )
    out = b"".join(bytes(a) for a in reader(0, len(data)))
    assert out == data
    assert reader.stats.retries > 0, "seed 5 @ 40% must fault at least once"
    assert len(rec.events_named("part_retry")) == reader.stats.retries
    spans = rec.summary()["spans"]
    assert spans["part_wait"]["count"] == reader.stats.parts == 16
    m = rec.metrics.summary()["counters"]
    assert m["remote_parts"] == 16 and m["remote_bytes"] == len(data)
    validate_trace(rec.trace_json())


def test_stop_scanner_records_fenced_spans():
    from repro.serve.engine import StopScanner

    rec = Recorder(enabled=True, fence=True)
    sc = StopScanner([b"ab"], batch=2, max_new=8, recorder=rec)
    hits = []
    for step, byte in enumerate(b"xaab"):
        hits.append(sc.scan(np.array([byte, ord("x")]), step))
    assert hits[3][0, 0] and not hits[3][1, 0]
    assert rec.summary()["spans"]["stop_scan"]["count"] == sc.dispatch_count == 4
    assert rec.metrics.summary()["counters"]["stop_scan_dispatches"] == 4


# -- the profiler's clock: spans as host events in the .xplane.pb ----------

CHUNK_SPANS = ("host_prep", "device_put", "dispatch", "wait", "fetch",
               "positions")


def _profiled(tmp_path, fn):
    """Run ``fn`` under ``jax.profiler.trace``; return its result and the
    names of the host events named ``repro.*``, as ProfileData reads them."""
    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names = [
        ev.name
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith("repro.")
    ]
    return out, names


def _chunk_ids(rec, name):
    return [args.get("chunk") for rows in rec.trace.snapshot().values()
            for _ph, n, _ts, _dur, args in rows if n == name]


def test_locate_spans_land_on_the_profiler_clock(rng, tmp_path):
    """Every per-chunk span of a traced locate scan is one host event named
    exactly ``repro.<span>`` per chunk, and all carry one chunk id each."""
    text, plans = _corpus(rng, n=50_000)
    rec = Recorder(enabled=True, fence=False)
    sc = StreamScanner(plans, 8192, recorder=rec)
    _, names = _profiled(tmp_path, lambda: sc.positions_many(text))
    n = sc.dispatch_count
    assert n == -(-(len(text) - sc.overlap) // sc.step_bytes) > 4
    assert sorted(set(names)) == sorted(f"repro.{s}" for s in CHUNK_SPANS)
    for span in CHUNK_SPANS:
        assert names.count(f"repro.{span}") == n, span
        assert _chunk_ids(rec, span) == list(range(n)), span


def test_count_spans_share_chunk_ids_and_fetch_once(rng, tmp_path):
    text, plans = _corpus(rng, n=50_000)
    rec = Recorder(enabled=True, fence=False)
    sc = StreamScanner(plans, 8192, recorder=rec)
    got, names = _profiled(tmp_path, lambda: sc.count_many(text))
    want = StreamScanner(plans, 8192).count_many(text)
    np.testing.assert_array_equal(got, want)
    n = sc.dispatch_count
    for span in ("host_prep", "device_put", "dispatch"):
        assert names.count(f"repro.{span}") == n
        assert _chunk_ids(rec, span) == list(range(n))
    assert names.count("repro.fetch") == 1  # the accumulator, once per scan
    assert "repro.wait" not in names and "repro.positions" not in names
    counters = rec.metrics.summary()["counters"]
    assert counters["fetch_bytes"] == got.nbytes
    assert counters["bytes_scanned"] == len(text)
    assert counters["put_bytes"] == n * sc.window_bytes


def test_disabled_recorder_makes_no_profiler_annotation(rng, tmp_path,
                                                        monkeypatch):
    import jax

    def refuse(*args, **kwargs):
        raise AssertionError("a disabled recorder annotated the profile")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    text, plans = _corpus(rng, n=30_000)
    sc = StreamScanner(plans, 8192, recorder=Recorder(enabled=False))
    _, names = _profiled(
        tmp_path, lambda: (sc.positions_many(text), sc.count_many(text))
    )
    assert names == []
    with pytest.raises(AssertionError):
        with Recorder(enabled=True).span("host_prep"):
            pass


def test_fetch_bytes_count_every_mask_copied(rng):
    text, plans = _corpus(rng, n=50_000)
    rec = Recorder(enabled=True, fence=False)
    sc = StreamScanner(plans, 8192, recorder=rec)
    sc.positions_many(text)
    counters = rec.metrics.summary()["counters"]
    assert counters["fetch_bytes"] == (
        sc.dispatch_count * sc.n_patterns * sc.window_bytes
    )
    assert counters["put_bytes"] == sc.dispatch_count * sc.window_bytes
    assert counters["bytes_scanned"] == len(text)
    assert "dispatches" not in counters


@pytest.mark.parametrize("fence", [False, True])
def test_enabled_recorder_leaves_answers_unchanged(rng, fence):
    text, plans = _corpus(rng, n=50_000)
    want_pos = StreamScanner(plans, 8192).positions_many(text)
    want_counts = StreamScanner(plans, 8192).count_many(text)
    rec = Recorder(enabled=True, fence=fence)
    got_pos = StreamScanner(plans, 8192, recorder=rec).positions_many(text)
    got_counts = StreamScanner(plans, 8192, recorder=rec).count_many(text)
    np.testing.assert_array_equal(got_counts, want_counts)
    assert len(got_pos) == len(want_pos)
    for g, w in zip(got_pos, want_pos):
        np.testing.assert_array_equal(g, w)


def test_span_writes_no_histogram():
    rec = Recorder(enabled=True, fence=False)
    with rec.span("dispatch", chunk=0):
        pass
    assert rec.metrics.summary()["histograms"] == {}
    assert rec.summary()["spans"]["dispatch"]["count"] == 1


# -- metrics + export plumbing ---------------------------------------------


def test_metrics_summary_and_report():
    m = Metrics()
    m.count("dispatches")
    m.count("bytes", 100)
    m.count("bytes", 50)
    m.gauge("chunk", 4096)
    for v in range(1, 101):
        m.observe("lat", float(v))
    s = m.summary()
    assert s["counters"] == {"bytes": 150, "dispatches": 1}
    assert s["gauges"] == {"chunk": 4096}
    h = s["histograms"]["lat"]
    assert h["count"] == 100 and h["min"] == 1 and h["max"] == 100
    assert h["p50"] == 51 and h["p99"] == 100 and h["mean"] == 50.5
    assert m.report() == m.report()  # deterministic
    assert "counter" in m.report() and "hist" in m.report()


def test_chrome_export_structure_and_nesting():
    rec = Recorder(enabled=True, fence=False)
    with rec.span("outer", lane="laneA", k=1):
        with rec.span("inner", lane="laneA"):
            pass
    with rec.span("other", lane="laneB"):
        pass
    rec.event("steal", victim=0, thief=2, start=0, stop=8)
    trace = rec.trace_json()
    assert trace["displayTimeUnit"] == "ms"
    validate_trace(trace)
    evs = trace["traceEvents"]
    meta = {e["args"]["name"]: e["tid"] for e in evs if e["ph"] == "M"}
    # the instant event fell on the thread-name fallback lane (MainThread)
    assert {"laneA", "laneB"} < set(meta)
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert xs["inner"]["tid"] == xs["outer"]["tid"] == meta["laneA"]
    assert xs["outer"]["ts"] <= xs["inner"]["ts"]
    assert (xs["inner"]["ts"] + xs["inner"]["dur"]
            <= xs["outer"]["ts"] + xs["outer"]["dur"] + 0.0011)
    (inst,) = [e for e in evs if e["ph"] == "i"]
    assert inst["name"] == "steal" and inst["s"] == "t"
    assert rec.report().startswith("== scan telemetry ==")


def test_validator_rejects_each_violation_class():
    def lane_meta(tid=1):
        return {"name": "thread_name", "ph": "M", "pid": 0, "tid": tid,
                "args": {"name": f"lane{tid}"}}

    def x(name, ts, dur, tid=1):
        return {"name": name, "ph": "X", "pid": 0, "tid": tid,
                "ts": ts, "dur": dur}

    ok = {"displayTimeUnit": "ms",
          "traceEvents": [lane_meta(), x("a", 0.0, 10.0), x("b", 1.0, 2.0)]}
    assert validate_trace(ok) == 3

    bad = [
        ["not an object"],
        {"traceEvents": [lane_meta()]},                      # no time unit
        {"displayTimeUnit": "ms", "traceEvents": []},        # empty
        {"displayTimeUnit": "ms",                            # unnamed tid
         "traceEvents": [x("a", 0.0, 1.0, tid=9)]},
        {"displayTimeUnit": "ms",                            # X without dur
         "traceEvents": [lane_meta(),
                         {"name": "a", "ph": "X", "pid": 0, "tid": 1,
                          "ts": 0.0}]},
        {"displayTimeUnit": "ms",                            # overlap, no nest
         "traceEvents": [lane_meta(), x("a", 0.0, 5.0), x("b", 3.0, 5.0)]},
        {"displayTimeUnit": "ms",                            # steal sans range
         "traceEvents": [lane_meta(),
                         {"name": "steal", "ph": "i", "pid": 0, "tid": 1,
                          "ts": 0.0, "s": "t", "args": {"victim": 0}}]},
        {"displayTimeUnit": "ms",                            # retry w/o attempt
         "traceEvents": [lane_meta(),
                         {"name": "retry", "ph": "i", "pid": 0, "tid": 1,
                          "ts": 0.0, "s": "t", "args": {"task": "x"}}]},
    ]
    for trace in bad:
        with pytest.raises(TraceSchemaError):
            validate_trace(trace)


def test_compile_ms_accounting():
    """timeit_median's warmup call (jit compile) lands in the BENCH meta as
    compile_ms instead of polluting the GB/s medians (satellite: warmup
    accounting fix)."""
    from benchmarks.run import drain_compile_ms, timeit_median

    drain_compile_ms()  # isolate from any earlier labels
    calls = []

    def fn():
        calls.append(1)
        if len(calls) == 1:
            time.sleep(0.02)  # the "compile"

    dt = timeit_median(fn, reps=3, label="obs/test")
    assert dt < 0.02, "warmup time must not leak into the median"
    ms = drain_compile_ms()
    assert set(ms) == {"obs/test"} and ms["obs/test"] >= 15.0
    assert drain_compile_ms() == {}  # drained


def test_default_recorder_is_shared_disabled_null():
    assert NULL.enabled is False
    from repro.core import shard_stream as shard_mod
    from repro.core import stream as stream_mod

    for mod in (stream_mod, shard_mod):
        assert mod._DEFAULT_REC.enabled is False
        assert mod._DEFAULT_REC.sinks  # log lines survive as a sink
