"""serve_grep — grep-as-a-service demo: in-process JSON-lines server,
concurrent clients, coalesced engine dispatches (repro.serve.query_plane,
DESIGN.md §15; operator guide in docs/serving.md).

    PYTHONPATH=src python examples/serve_grep.py [--queries 400]
                                                 [--clients 16]
                                                 [--size 500000]
                                                 [--trace service_trace.json]

Starts a :class:`GrepServer` on an ephemeral localhost port, loads two
synthetic corpora, and fires --queries grep queries from --clients
concurrent connections with skewed pattern popularity.  Spot-checked
responses must equal a host ``bytes.find`` reference, then
the run prints QPS, request-latency p50/p99, and the coalescing ratio.
--trace exports the flight-recorder view of the run — the same artifact CI
validates with benchmarks/validate_trace.py.
"""

from __future__ import annotations

import argparse
import asyncio
import time

import numpy as np

from repro.compile_cache import configure_compile_cache
from repro.core.baselines import find_all
from repro.obs.recorder import Recorder
from repro.serve.query_plane import QueryPlane, ServiceConfig
from repro.serve.server import GrepClient, GrepServer

WORDS = [b"error", b"warn", b"timeout", b"retry", b"disk", b"net", b"oomkill"]


def make_corpus(size: int, seed: int) -> bytes:
    rng = np.random.RandomState(seed)
    text = rng.randint(97, 123, size=size).astype(np.uint8)
    for w in WORDS * max(1, size // 20_000):
        pos = int(rng.randint(0, size - 32))
        text[pos : pos + len(w)] = np.frombuffer(w, np.uint8)
    return text.tobytes()


async def run(args) -> None:
    rng = np.random.RandomState(11)
    corpora = {f"logs{i}": make_corpus(args.size, i) for i in range(2)}
    rec = Recorder(enabled=bool(args.trace), fence=bool(args.trace))
    plane = QueryPlane(
        ServiceConfig(coalesce_ms=2.0, max_batch=64), recorder=rec
    )
    # skewed popularity: a few hot patterns dominate, like real query logs
    weights = 1.0 / np.arange(1, len(WORDS) + 1) ** 1.2
    weights /= weights.sum()

    async with GrepServer(plane) as (host, port):
        clients = [
            await GrepClient.connect(host, port) for _ in range(args.clients)
        ]
        for cid, text in corpora.items():
            await clients[0].add_corpus(cid, text)

        latencies: list = []
        checked = [0]

        async def worker(wi: int, n: int) -> None:
            wrng = np.random.RandomState(100 + wi)
            for _ in range(n):
                cid = f"logs{int(wrng.randint(0, 4) == 0)}"
                pats = [
                    WORDS[i]
                    for i in wrng.choice(
                        len(WORDS), size=1 + wrng.randint(0, 3),
                        replace=False, p=weights,
                    )
                ]
                t0 = time.perf_counter()
                resp = await clients[wi].query(cid, pats)
                latencies.append((time.perf_counter() - t0) * 1e3)
                assert resp["ok"], resp
                if checked[0] < 25:  # spot-check against the host reference
                    checked[0] += 1
                    want = [len(find_all(corpora[cid], p)) for p in pats]
                    assert resp["counts"] == want, (pats, resp, want)

        per = -(-args.queries // args.clients)
        t0 = time.perf_counter()
        await asyncio.gather(*[worker(i, per) for i in range(args.clients)])
        wall = time.perf_counter() - t0

        stats = (await clients[0].stats())["stats"]
        for c in clients:
            await c.close()

    lat = np.sort(np.asarray(latencies))
    total = len(latencies)
    print(
        f"{total} queries from {args.clients} clients over "
        f"{len(corpora)} x {args.size / 1e6:.1f} MB corpora in {wall:.2f}s"
    )
    print(
        f"QPS {total / wall:,.0f}   p50 {lat[total // 2]:.2f} ms   "
        f"p99 {lat[min(total - 1, int(total * 0.99))]:.2f} ms"
    )
    print(
        f"dispatches: {stats['dispatches']} for {stats['requests']} requests"
        f" (coalescing ratio {stats['coalescing_ratio']:.1f}x, "
        f"{stats['result_cache_hits']} result-cache hits)"
    )
    assert checked[0] > 0 and stats["dispatches"] < stats["requests"]
    if args.trace:
        out = rec.export_trace(args.trace)
        print(f"trace written to {out} (validate: benchmarks/validate_trace.py)")
    print("ok — coalesced answers match the host reference")


def main() -> None:
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", type=int, default=400)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--size", type=int, default=500_000)
    ap.add_argument("--trace", type=str, default=None)
    args = ap.parse_args()
    asyncio.run(run(args))


if __name__ == "__main__":
    main()
