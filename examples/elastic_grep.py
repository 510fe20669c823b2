"""elastic_grep — exact scans over a flaky object store (DESIGN.md §12).

    PYTHONPATH=src python examples/elastic_grep.py [--size 8000000]
        [--shards 0] [--chunk 4194304] [--fault-rate 0.05] [--seed 0]
        [--trace TRACE.json]

The whole elastic fabric in one run: the corpus lives behind a
FakeObjectStore (a range-GET "RPC" with injected faults), a
RemoteRangeReader fetches it in prefetched parts with per-part timeout and
classified backoff retry, and a ShardedStreamScanner with work stealing
scans it — shard crashes injected inside the retry scope, straggling shards
shedding trailing ranges to idle lanes.  Counts must equal the clean
single-host StreamScanner bit-for-bit despite every injected fault.

Then the degraded path: the faults are made PERMANENT, and the same scan
with on_exhausted="partial" returns a PartialScanResult naming exactly
which byte ranges were lost instead of raising.

Run under XLA_FLAGS=--xla_force_host_platform_device_count=8 (the CI chaos
job does) to see the lanes spread over devices.  With --trace PATH the run
attaches a flight recorder (repro.obs, DESIGN.md §13) and exports a
Chrome/Perfetto trace: per-lane span tracks, one retry event per injected
fault, every steal/shed with its exact byte range — open it in
https://ui.perfetto.dev.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

ALPHA = 64  # corpus alphabet [0, 64); queries use byte 200


def make_queries():
    rng = np.random.RandomState(7)
    qs = []
    for m in (8, 16):
        q = rng.randint(0, ALPHA, size=m).astype(np.uint8)
        q[m // 2] = 200  # impossible in the corpus: hits == plants, exactly
        qs.append(q)
    return qs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=8_000_000)
    ap.add_argument("--chunk", type=int, default=1 << 22)
    ap.add_argument("--shards", type=int, default=0, help="0 = one per device")
    ap.add_argument("--fault-rate", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", type=str, default=None,
                    help="export a Perfetto trace of the faulty scan here")
    args = ap.parse_args()

    import jax

    from repro.compile_cache import configure_compile_cache
    from repro.core import engine
    from repro.core.remote_source import FakeObjectStore
    from repro.core.shard_stream import PartialScanResult, ShardedStreamScanner
    from repro.core.stream import StreamScanner
    from repro.dist.fault_injection import FaultPlan
    from repro.dist.fault_tolerance import BackoffPolicy
    from repro.obs import Recorder

    configure_compile_cache()
    queries = make_queries()
    plans = engine.compile_patterns(queries)

    text = np.random.RandomState(1000).randint(
        0, ALPHA, size=args.size
    ).astype(np.uint8)
    rng = np.random.RandomState(3)
    planted = [0] * len(queries)
    for _ in range(200):  # scatter plants so every shard owns some
        qi = rng.randint(len(queries))
        q = queries[qi]
        s = rng.randint(0, args.size - len(q))
        if (text[s : s + len(q)] == 200).any() or 200 in q[:0]:
            continue
        if (text[max(0, s - 16) : s + len(q) + 16] == 200).any():
            continue  # keep plants disjoint from each other
        text[s : s + len(q)] = q
        planted[qi] += 1

    want = StreamScanner(plans, args.chunk).count_many(text)

    r = args.fault_rate
    plan = FaultPlan(
        args.seed, read_error_rate=r, truncate_rate=r, crash_rate=r,
        attempts_per_fault=1,
    )
    rec = Recorder(enabled=True, fence=False) if args.trace else None
    store = FakeObjectStore(text, plan=plan)
    reader = store.reader(part_bytes=1 << 20, prefetch=3, retries=4,
                          timeout_s=30.0, recorder=rec)
    sc = ShardedStreamScanner(
        plans, args.shards or None, args.chunk, max_retries=16,
        fault_plan=plan, steal=True, min_steal_bytes=1 << 16,
        backoff=BackoffPolicy(base_s=0.001, seed=args.seed),
        recorder=rec,
    )
    print(
        f"{args.size / 1e6:.0f} MB corpus behind a faulty object store "
        f"({r:.0%} read errors + truncations + shard crashes per site), "
        f"{sc.n_shards} shards over {jax.device_count()} device(s), "
        f"work stealing ON"
    )
    t0 = time.perf_counter()
    counts = sc.count_many(reader)
    dt = time.perf_counter() - t0
    faults = plan.counts_by_action()
    print(
        f"elastic scan: {dt:.2f}s ({args.size / dt / 1e9:.3f} GB/s)  "
        f"injected={faults}  shard_retries={len(sc.events)}  "
        f"part_retries={reader.stats.retries}  steals={len(sc.steal_events)}"
    )
    if not np.array_equal(counts, want):
        raise SystemExit("FAIL: recovered counts != clean oracle")
    for qi, n in zip(sc.order, counts):
        print(f"query {qi} (m={len(queries[qi])}): {int(n)} hits "
              f"({planted[qi]} planted)")
    print("recovered counts are bit-identical to the clean scan")

    if rec is not None:
        rec.export_trace(args.trace)
        evs = {k: len(rec.events_named(k))
               for k in ("fault", "retry", "steal", "shed", "range_done")}
        done = sorted(
            (e["start"], e["stop"]) for e in rec.events_named("range_done")
        )
        covered = sum(e - s for s, e in done)
        print(
            f"trace -> {args.trace}  events: "
            + "  ".join(f"{k}={v}" for k, v in evs.items() if v)
            + f"  range_done coverage: {covered}/{args.size} bytes"
        )

    # -- graceful degradation: permanent faults, partial result -------------
    perm = FaultPlan(args.seed + 1, crash_rate=0.3, attempts_per_fault=None)
    sc2 = ShardedStreamScanner(
        plans, args.shards or None, args.chunk, max_retries=1,
        fault_plan=perm, on_exhausted="partial",
    )
    res = sc2.count_many(text)
    assert isinstance(res, PartialScanResult)
    print(
        f"permanent crashes + on_exhausted='partial': "
        f"covered {res.coverage_fraction():.0%} "
        f"({len(res.missing)} missing range(s): "
        f"{[(int(s), int(e)) for s, e in res.missing]})"
    )
    if res.complete:
        print("  (this seed killed no shard — rerun with another --seed)")
    print("ELASTIC_GREP_OK — exact under faults, explicit when degraded")


if __name__ == "__main__":
    main()
