"""chip_smoke — drive the grep service and the stream scan once on a TPU, at
corpus sizes a log-search node holds, and check every answer against a host
reference that shares no code with the engine.

    python chip_smoke.py              # one chip: service + stream phases
    python chip_smoke.py --chips 4    # sharded stream scan over four chips
                                      # against the one-chip StreamScanner
    python chip_smoke.py --small      # every phase at a tiny size; on a
                                      # machine without a TPU it then fails

Phases (one process, data generated from --seed, nothing downloaded):

  1. service: a GrepServer over a QueryPlane holding a 1 GiB count corpus and
     a 64 MiB match corpus; concurrent GrepClients send count queries with
     m from 2 to 32 (EPSMa, EPSMb and EPSMc groups) and match queries.
     Every reply must equal ``baselines.find_all`` (a ``bytes.find`` loop).
  2. stream: a StreamScanner with chunk_bytes="auto" counts and locates
     queries over a 4 GiB stream generated chunk by chunk.  Each query holds
     a byte the corpus never uses and is planted across window seams, so
     the expected counts and positions are the plants themselves.

With --chips 4 only the sharded stream scan runs: a ShardedStreamScanner
with one shard per chip and the one-chip StreamScanner count the same
stream, whose plants also cross every shard boundary and every shard's own
window seams; both must equal the plants.

The last line of standard output is ``{"ok": true, "device": {...}}``; it is
printed only when every check passed on a TPU.  Every earlier line is smoke
output (sizes, wall times, compile time, memory), not a benchmark number.
"""

from __future__ import annotations

import argparse
import asyncio
import bisect
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
from jax import monitoring  # noqa: E402

from repro.compile_cache import configure_compile_cache  # noqa: E402
from repro.core import engine  # noqa: E402
from repro.core.baselines import find_all  # noqa: E402
from repro.core.shard_stream import ShardedStreamScanner  # noqa: E402
from repro.core.stream import StreamScanner  # noqa: E402
from repro.obs.recorder import Recorder  # noqa: E402
from repro.serve.query_plane import QueryPlane, ServiceConfig  # noqa: E402
from repro.serve.server import GrepClient, GrepServer  # noqa: E402

SIZES = {
    # count corpus, match corpus, stream bytes
    "full": (1 << 30, 1 << 26, 4 << 30),
    "small": (1 << 21, 1 << 21, 24 << 20),
}
# Query lengths of the service phase: m = 2 .. 32 over EPSMa (m < 4), EPSMb
# (m < 16) and EPSMc.  Each length is one plan group of a coalesced union,
# and the union's compile time grows with its group count (measured for
# v5e: ~25 s at these 8 groups, minutes at 31), so the set stays small.
LENGTHS = (2, 3, 5, 8, 12, 16, 24, 32)
# Log-like words planted into the service corpora, one or two per length.
WORDS = [
    b"ok", b"gc", b"err", b"oom", b"fatal", b"panic", b"timeout!",
    b"segfault", b"disk is full", b"conn refused", b"retrying request",
    b"too many open files (24)", b"no space left on device!",
    b"failed to pull image from regist",
]
GEN_BYTES = 1 << 26  # stream generation block
N_CLIENTS = 16
N_COUNT_REQUESTS = 42
N_MATCH_REQUESTS = 6
STREAM_QUERY_LENGTHS = (3, 9, 15, 27)  # EPSMa, EPSMb, EPSMb, EPSMc


def log(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


class CompileClock:
    """Sums the backend compile time JAX reports while it is installed."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def device_memory(device) -> dict:
    stats = device.memory_stats() or {}
    keys = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
    return {k: stats[k] for k in keys if k in stats}


# -- phase 1: the service ---------------------------------------------------


def log_corpus(n: int, seed: int) -> bytes:
    """Lowercase text with every WORDS entry planted many times."""
    rng = np.random.default_rng([seed, n])
    text = rng.integers(97, 123, size=n, dtype=np.uint8)
    for w in WORDS:
        starts = rng.integers(0, n - len(w), size=max(4, n >> 16))
        text[starts[:, None] + np.arange(len(w))] = np.frombuffer(w, np.uint8)
    return text.tobytes()


def query_pool(buf: bytes, rng) -> list:
    """Patterns with m = 2 .. 32: the planted words, substrings of the
    corpus (at least one hit each), and strings that are mostly absent."""
    pool = list(WORDS)
    for m in LENGTHS:
        s = int(rng.integers(0, len(buf) - m))
        pool.append(buf[s : s + m])
        pool.append(rng.integers(97, 123, size=m, dtype=np.uint8).tobytes())
    return list(dict.fromkeys(pool))


def service_requests(count_buf: bytes, match_buf: bytes, seed: int) -> list:
    """(corpus, mode, patterns) requests, count and match interleaved."""
    rng = np.random.default_rng([seed, 1])
    cpool = query_pool(count_buf, rng)
    mpool = query_pool(match_buf, rng)
    reqs = []
    for _ in range(N_COUNT_REQUESTS):
        k = int(rng.integers(1, 9))
        pats = [cpool[i] for i in rng.choice(len(cpool), k, replace=False)]
        reqs.append(("count_corpus", "count", pats))
    for i in range(N_MATCH_REQUESTS):
        k = int(rng.integers(1, 4))
        pats = [mpool[j] for j in rng.choice(len(mpool), k, replace=False)]
        reqs.insert(8 * i + 3, ("match_corpus", "match", pats))
    lengths = {len(p) for _, mode, ps in reqs if mode == "count" for p in ps}
    # every regime's group must run: EPSMa (m < 4), EPSMb (< 16), EPSMc
    if not (min(lengths) < 4 and max(lengths) >= 16
            and any(4 <= m < 16 for m in lengths)):
        raise SystemExit(f"FAIL: count queries miss a regime: {lengths}")
    return reqs


async def serve_and_query(corpora: dict, reqs: list):
    # room for every index (4.5 device bytes per corpus byte): no eviction
    budget = sum(5 * len(b) for b in corpora.values())
    rec = Recorder(enabled=True, fence=False)
    plane = QueryPlane(
        ServiceConfig(corpus_budget_bytes=budget), recorder=rec
    )
    for cid, buf in corpora.items():
        t0 = time.perf_counter()
        plane.add_corpus(cid, buf)
        log(f"service: loaded {cid} ({len(buf)} B) in "
            f"{time.perf_counter() - t0:.2f} s")
    replies = [None] * len(reqs)
    async with GrepServer(plane) as (host, port):
        clients = [
            await GrepClient.connect(host, port) for _ in range(N_CLIENTS)
        ]

        async def client_loop(ci: int) -> None:
            for ri in range(ci, len(reqs), N_CLIENTS):
                cid, mode, pats = reqs[ri]
                replies[ri] = await clients[ci].query(cid, pats, mode=mode)

        t0 = time.perf_counter()
        await asyncio.gather(*[client_loop(i) for i in range(N_CLIENTS)])
        wall = time.perf_counter() - t0
        stats = await clients[0].stats()
        for c in clients:
            await c.close()
    return replies, wall, stats


def check_replies(corpora: dict, reqs: list, replies: list) -> int:
    """Every reply against find_all; returns the number of patterns checked."""
    ref: dict = {}
    checked = 0
    for (cid, mode, pats), reply in zip(reqs, replies):
        if not reply["ok"]:
            raise SystemExit(f"FAIL: service reply not ok: {reply}")
        for i, p in enumerate(pats):
            key = (cid, p)
            if key not in ref:
                ref[key] = find_all(corpora[cid], p)
            want = ref[key]
            if reply["counts"][i] != len(want):
                raise SystemExit(
                    f"FAIL: {cid} count of {p!r}: service "
                    f"{reply['counts'][i]}, reference {len(want)}"
                )
            if mode == "match" and not np.array_equal(
                np.asarray(reply["positions"][i], np.int64), want
            ):
                raise SystemExit(f"FAIL: {cid} positions of {p!r} differ")
            checked += 1
    return checked


def phase_service(count_bytes: int, match_bytes: int, *, seed: int) -> None:
    t0 = time.perf_counter()
    corpora = {
        "count_corpus": log_corpus(count_bytes, seed),
        "match_corpus": log_corpus(match_bytes, seed + 1),
    }
    reqs = service_requests(
        corpora["count_corpus"], corpora["match_corpus"], seed
    )
    log(f"service: generated corpora in {time.perf_counter() - t0:.2f} s; "
        f"{len(reqs)} requests from {N_CLIENTS} clients")
    replies, wall, stats = asyncio.run(serve_and_query(corpora, reqs))
    s = stats["stats"]
    log(f"service: {s['requests']} requests answered in {wall:.2f} s, "
        f"{s['dispatches']} dispatches, coalescing ratio "
        f"{s['coalescing_ratio']:.2f}, resident {s['corpus_bytes']} B")
    log(f"service: slo_report {json.dumps(stats['slo'], sort_keys=True)}")
    t0 = time.perf_counter()
    n = check_replies(corpora, reqs, replies)
    log(f"service: {n} pattern answers equal the find_all reference "
        f"(checked in {time.perf_counter() - t0:.2f} s)")


# -- phase 2: the stream ----------------------------------------------------


def stream_queries(seed: int) -> list:
    """One query per length; query i holds byte 0xF0 + i, which the
    lowercase stream never uses, so it matches exactly where planted."""
    rng = np.random.default_rng([seed, 2])
    qs = []
    for i, m in enumerate(STREAM_QUERY_LENGTHS):
        q = rng.integers(97, 123, size=m, dtype=np.uint8)
        q[int(rng.integers(0, m))] = 0xF0 + i
        qs.append(q.tobytes())
    return qs


def window_seams(sc: StreamScanner, start: int, stop: int) -> list:
    """Positions in [start, stop) where a window of ``sc`` begins its new
    bytes, for a scan of that range that carries an overlap prefix when
    start > 0, as each shard of a ShardedStreamScanner does.  From 0, the
    first window takes overlap + step new bytes, each later one step."""
    first = start if start > 0 else sc.overlap + sc.step_bytes
    return list(range(first, stop, sc.step_bytes))


def plant_sites(seam_groups, total: int, seed: int, queries) -> list:
    """Sorted (start, query) plants, at least 64 bytes apart: one across
    each seam of each group, the seams of a group taking the query phases
    in turn, then random interior positions.  An earlier group keeps its
    plant where a later one would come too close."""
    rng = np.random.default_rng([seed, 3])
    groups = []
    for seams in seam_groups:
        group = []
        for j, seam in enumerate(seams):
            q = j % len(queries)
            phase = (j // len(queries)) % (len(queries[q]) - 1)
            group.append((seam - 1 - phase, q))
        groups.append(group)
    groups.append([
        (int(s), int(rng.integers(0, len(queries))))
        for s in rng.integers(0, total - 64, size=max(16, total >> 22))
    ])
    taken, sites = [], []
    for group in groups:
        for s, q in group:
            i = bisect.bisect_left(taken, s)
            near = (i < len(taken) and taken[i] - s < 64) or (
                i > 0 and s - taken[i - 1] < 64
            )
            if s >= 0 and s + 64 <= total and not near:
                taken.insert(i, s)
                sites.append((s, q))
    return sorted(sites)


def straddling(sites, queries, seams) -> int:
    """How many of ``seams`` a planted query crosses (starts before the
    seam, ends at or after it)."""
    starts = [s for s, _ in sites]
    hit = 0
    for b in seams:
        i = bisect.bisect_left(starts, b) - 1
        if i >= 0 and starts[i] + len(queries[sites[i][1]]) > b:
            hit += 1
    return hit


class SeededStream:
    """Range source ``(start, stop) -> chunks`` over a stream that any range
    of regenerates identically: GEN_BYTES blocks of lowercase bytes drawn
    from (seed, block index), with the plants written over them."""

    def __init__(self, total: int, seed: int, queries: list, sites: list):
        self.total_bytes = total
        self.seed = seed
        self.queries = queries
        self.starts = np.asarray([s for s, _ in sites], np.int64)
        self.which = [q for _, q in sites]

    def block(self, b: int) -> np.ndarray:
        lo = b * GEN_BYTES
        n = min(GEN_BYTES, self.total_bytes - lo)
        rng = np.random.default_rng([self.seed, 4, b])
        out = rng.integers(97, 123, size=n, dtype=np.uint8)
        first = np.searchsorted(self.starts, lo - 64)
        last = np.searchsorted(self.starts, lo + n)
        for j in range(first, last):
            q = np.frombuffer(self.queries[self.which[j]], np.uint8)
            s = int(self.starts[j]) - lo
            a, e = max(s, 0), min(s + len(q), n)
            if a < e:
                out[a:e] = q[a - s : e - s]
        return out

    def __call__(self, start: int, stop: int):
        for b in range(start // GEN_BYTES, -(-stop // GEN_BYTES)):
            lo = b * GEN_BYTES
            blk = self.block(b)
            yield blk[max(start - lo, 0) : stop - lo]

    def expected(self):
        """Per-query sorted plant starts (original query order)."""
        return [
            self.starts[[w == i for w in self.which]]
            for i in range(len(self.queries))
        ]


def stream_setup(total: int, seed: int, chunk_bytes):
    queries = stream_queries(seed)
    plans = engine.compile_patterns(queries)
    return queries, plans, StreamScanner(plans, chunk_bytes)


def in_query_order(rows, order):
    out = [None] * len(rows)
    for r, q in enumerate(order):
        out[q] = rows[r]
    return out


def phase_stream(total: int, *, seed: int) -> None:
    dev = jax.devices()[0]
    log(f"stream: memory_stats before the scan {device_memory(dev)}")
    queries, _plans, sc = stream_setup(total, seed, "auto")
    seams = window_seams(sc, 0, total)
    sites = plant_sites([seams], total, seed, queries)
    source = SeededStream(total, seed, queries, sites)
    log(f"stream: plants cross {straddling(sites, queries, seams)} of "
        f"{len(seams)} window seams")
    log(f"stream: chunk_bytes='auto' chose {sc.chunk_bytes} B "
        f"(window {sc.window_bytes} B, step {sc.step_bytes} B), "
        f"use_kernel=False (the fused XLA step; no Pallas kernel)")
    want = source.expected()
    log(f"stream: {total} B, queries m={[len(q) for q in queries]}, "
        f"plants {[len(w) for w in want]}")
    t0 = time.perf_counter()
    counts = in_query_order(sc.count_many(source(0, total)), sc.order)
    log(f"stream: count pass {time.perf_counter() - t0:.2f} s, "
        f"{sc.dispatch_count} dispatches")
    for q, c, w in zip(queries, counts, want):
        if c != len(w):
            raise SystemExit(f"FAIL: stream count of {q!r}: {c} != {len(w)}")
    t0 = time.perf_counter()
    pos = in_query_order(sc.positions_many(source(0, total)), sc.order)
    log(f"stream: positions pass {time.perf_counter() - t0:.2f} s")
    for q, p, w in zip(queries, pos, want):
        if not np.array_equal(p, w):
            raise SystemExit(f"FAIL: stream positions of {q!r} differ")
    log(f"stream: counts {[int(c) for c in counts]} and every position "
        f"equal the plants")


# -- four chips: the sharded stream scan ------------------------------------


def phase_sharded(total: int, n_chips: int, *, seed: int) -> None:
    devices = jax.local_devices()
    if len(devices) < n_chips:
        raise SystemExit(f"FAIL: {n_chips} chips asked, {len(devices)} found")
    devices = devices[:n_chips]
    queries, plans, one = stream_setup(total, seed, "auto")
    rec = Recorder(enabled=True, fence=False)
    sharded = ShardedStreamScanner(
        plans, n_chips, one.chunk_bytes, devices=devices, recorder=rec
    )
    # Plants across the shard boundaries first (each shard's overlap prefix
    # must hand such an occurrence to exactly one shard), then across each
    # shard's own window seams, then across the one-chip scanner's.
    ranges = sharded.shard_spec(total).ranges
    bounds = [a for a, _ in ranges[1:]]
    shard_seams = [
        b for a, e in ranges for b in window_seams(one, a, e) if a == 0 or b > a
    ]
    one_seams = window_seams(one, 0, total)
    sites = plant_sites([bounds, shard_seams, one_seams], total, seed, queries)
    source = SeededStream(total, seed, queries, sites)
    crossed = straddling(sites, queries, bounds)
    log(f"sharded: shards {list(ranges)}; plants cross {crossed} of "
        f"{len(bounds)} shard boundaries, "
        f"{straddling(sites, queries, shard_seams)} of {len(shard_seams)} "
        f"shard window seams, {straddling(sites, queries, one_seams)} of "
        f"{len(one_seams)} one-chip window seams")
    if crossed != len(bounds):
        raise SystemExit("FAIL: a shard boundary has no plant across it")
    t0 = time.perf_counter()
    got = sharded.count_many(source, total_bytes=total)
    log(f"sharded: {n_chips} shards, {total} B in "
        f"{time.perf_counter() - t0:.2f} s, chunk {one.chunk_bytes} B")
    for ev in rec.events_named("range_done"):
        log(f"sharded: shard {ev['origin']} [{ev['start']}, {ev['stop']}) "
            f"dispatched on {ev['device']}")
    placed = {ev["device"] for ev in rec.events_named("range_done")}
    t0 = time.perf_counter()
    ref = one.count_many(source(0, total))
    log(f"sharded: one-chip StreamScanner {time.perf_counter() - t0:.2f} s")
    want = source.expected()
    planted = [len(want[q]) for q in one.order]  # engine row order
    log(f"sharded: counts {got.tolist()}, one-chip {ref.tolist()}, "
        f"planted {planted}")
    if not np.array_equal(got, ref) or ref.tolist() != planted:
        raise SystemExit("FAIL: sharded counts differ from the one-chip scan")
    if len(placed) != n_chips:
        raise SystemExit(f"FAIL: shards ran on {sorted(placed)}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--small", action="store_true",
                    help="tiny sizes: a rehearsal of every phase")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.small:
        raise SystemExit(f"no TPU: JAX found {dev.platform} devices only")
    cache = configure_compile_cache()
    clock = CompileClock()
    count_bytes, match_bytes, stream_bytes = SIZES[
        "small" if args.small else "full"
    ]
    log("smoke output, not benchmark numbers")
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
        f"jax {jax.__version__}, compile cache {cache}")
    if args.chips > 1:  # the sharded path and its one-chip reference only
        phases = [("sharded", phase_sharded, (stream_bytes, args.chips))]
    else:
        phases = [
            ("service", phase_service, (count_bytes, match_bytes)),
            ("stream", phase_stream, (stream_bytes,)),
        ]
    for name, run, sizes in phases:
        t0 = time.perf_counter()
        run(*sizes, seed=args.seed)
        log(f"{name}: phase wall {time.perf_counter() - t0:.2f} s; compile "
            f"so far {clock.seconds:.2f} s over {clock.count} programs; "
            f"memory {device_memory(dev)}")
    if not on_tpu:
        raise SystemExit(f"every phase ran, but on {dev.platform}, not a TPU")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))


if __name__ == "__main__":
    main()
