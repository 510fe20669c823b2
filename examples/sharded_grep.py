"""sharded_grep — one logical corpus, S shards, exact counts (DESIGN.md §10).

    PYTHONPATH=src python examples/sharded_grep.py [--size 64000000]
        [--shards 0] [--chunk 4194304] [--processes 1]

Range-partitions a --size byte corpus into --shards shards (0 = one per
device; run under XLA_FLAGS=--xla_force_host_platform_device_count=8 to see
per-shard device placement on a laptop), plants query occurrences straddling
EVERY interior shard boundary at cycling phases, and scans with a
ShardedStreamScanner.  The queries contain a byte outside the corpus
alphabet, so every hit is a planted one and the count check is exact across
all shard seams.  Single-host results are also checked against the plain
1-shard StreamScanner wall clock for the scaling printout.

With --processes N the script respawns itself as an N-process
jax.distributed cluster (the CI weekly slow job runs N=2): each process
scans the shards ``i % N == process_index`` and counts merge through the
multihost psum; positions go through the ragged all-gather.
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
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


def make_corpus(size: int, queries, boundaries):
    """The full corpus with each query planted straddling every interior
    shard boundary, queries and straddle phases cycling.  Returns (text,
    planted_counts, planted_positions)."""
    text = np.random.RandomState(1000).randint(0, ALPHA, size=size).astype(np.uint8)
    planted = [0] * len(queries)
    positions = [[] for _ in queries]
    last_end = -1
    for si, b in enumerate(boundaries):
        qi = si % len(queries)
        q = queries[qi]
        phase = 1 + (si % (len(q) - 1))  # 1..m-1: every seam relation occurs
        s = b - phase
        if s <= last_end or s < 0 or s + len(q) > size:
            continue
        text[s : s + len(q)] = q
        planted[qi] += 1
        positions[qi].append(s)
        last_end = s + len(q)
    return text, planted, [np.asarray(p, np.int64) for p in positions]


def spawn_cluster(args) -> int:
    """Respawn this script --processes times as a jax.distributed cluster."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coordinator = f"127.0.0.1:{port}"
    procs = []
    for pid in range(args.processes):
        cmd = [
            sys.executable, os.path.abspath(__file__),
            "--size", str(args.size), "--shards", str(args.shards),
            "--chunk", str(args.chunk), "--processes", str(args.processes),
            "--process-id", str(pid), "--coordinator", coordinator,
        ]
        procs.append(subprocess.Popen(cmd, env=os.environ.copy()))
    rc = 0
    for p in procs:
        rc |= p.wait()
    if rc:
        raise SystemExit(f"cluster process failed (rc={rc})")
    print(f"cluster of {args.processes} processes: all exited cleanly")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=64_000_000)
    ap.add_argument("--chunk", type=int, default=1 << 22)
    ap.add_argument("--shards", type=int, default=0, help="0 = one per device")
    ap.add_argument("--processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--coordinator", type=str, default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.processes > 1 and args.process_id is None:
        raise SystemExit(spawn_cluster(args))

    # joining a cluster must precede every other jax call
    from repro.launch.mesh import init_stream_cluster

    pid, nproc = init_stream_cluster(
        args.coordinator, args.processes, args.process_id
    )

    import jax

    from repro.compile_cache import configure_compile_cache
    from repro.core import engine
    from repro.core.shard_stream import ShardedStreamScanner
    from repro.core.stream import StreamScanner

    configure_compile_cache()
    queries = make_queries()
    plans = engine.compile_patterns(queries)
    sc = ShardedStreamScanner(plans, args.shards or None, args.chunk)
    spec = sc.shard_spec(args.size)
    boundaries = [s for s, _ in spec.ranges[1:]]
    text, planted, planted_pos = make_corpus(args.size, queries, boundaries)
    if pid == 0:
        print(
            f"{args.size / 1e6:.0f} MB corpus, {spec.n_shards} shards over "
            f"{jax.device_count()} device(s) x {nproc} process(es); "
            f"{sum(planted)} occurrences planted across "
            f"{len(boundaries)} shard seams"
        )

    t0 = time.perf_counter()
    counts = sc.count_many(text)
    dt = time.perf_counter() - t0
    pos = ShardedStreamScanner(plans, args.shards or None, args.chunk).positions_many(text)

    order = sc.order  # engine rows are plan-concatenated
    ok = all(counts[r] == planted[order[r]] for r in range(len(counts)))
    ok &= all(
        np.array_equal(pos[r], planted_pos[order[r]]) for r in range(len(counts))
    )
    if pid == 0:
        print(f"sharded scan: {dt:.2f}s  ({args.size / dt / 1e9:.3f} GB/s)")
        if nproc == 1:
            t0 = time.perf_counter()
            base = StreamScanner(plans, args.chunk).count_many(text)
            dt1 = time.perf_counter() - t0
            assert np.array_equal(base, counts), "sharded != 1-shard stream"
            print(
                f"1-shard stream: {dt1:.2f}s  "
                f"(sharded speedup {dt1 / dt:.2f}x)"
            )
        for r in range(len(counts)):
            qi = order[r]
            print(
                f"query {qi} (m={len(queries[qi])}): {int(counts[r])} hits, "
                f"{planted[qi]} planted (seam-straddling)"
            )
        if not ok:
            raise SystemExit("FAIL: sharded counts/positions != planted")
        print("SHARDED_GREP_OK — exact across all shard seams")
    elif not ok:
        raise SystemExit(f"FAIL on process {pid}")


if __name__ == "__main__":
    main()
