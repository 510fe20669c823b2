"""stream_grep — constant-memory exact grep over a corpus that never fits
on device (repro.core.stream, DESIGN.md §9).

    PYTHONPATH=src python examples/stream_grep.py [--size 1000000000]
                                                  [--chunk 4194304]

Generates a --size byte corpus CHUNK BY CHUNK (the full text never exists
anywhere — not on device, not on host), plants query occurrences straddling
the scanner's window seams, and streams the whole thing through a
StreamScanner: device memory stays O(--chunk) while the count is exact.
The queries contain a byte outside the corpus alphabet, so every hit is a
planted one and the count check is exact, seams included.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.compile_cache import configure_compile_cache
from repro.core import engine
from repro.core.stream import StreamScanner

GEN_CHUNK = 1 << 23  # host generation granularity (8 MiB)
ALPHA = 64           # corpus alphabet [0, 64); queries use byte 200


def make_queries():
    rng = np.random.RandomState(7)
    qs = []
    for m in (8, 16):
        q = rng.randint(0, ALPHA, size=m).astype(np.uint8)
        q[m // 2] = 200  # impossible in the corpus: hits == plants, exactly
        qs.append(q)
    return qs


def corpus(total: int, queries, seam_starts):
    """Yield uint8 chunks of a `total`-byte random corpus with each query
    planted at its seam-straddling start positions.  Plants that would cross
    a GENERATION chunk boundary are clipped to the next chunk's interior (a
    few positions shift; the planted count is returned via `planted`)."""
    planted = [0] * len(queries)
    pending = sorted(seam_starts, key=lambda sq: sq[0])
    base = 0
    i = 0
    while base < total:
        n = min(GEN_CHUNK, total - base)
        chunk = np.random.RandomState(1000 + i).randint(
            0, ALPHA, size=n
        ).astype(np.uint8)
        kept = []
        for start, qi in pending:
            q = queries[qi]
            if start < base:
                continue  # clipped away (crossed a generation boundary)
            if start + len(q) <= base + n:
                chunk[start - base : start - base + len(q)] = q
                planted[qi] += 1
            elif start < base + n:
                pass  # would straddle the generation seam: drop it
            else:
                kept.append((start, qi))
        pending = kept
        yield chunk
        base += n
        i += 1
    corpus.planted = planted  # smuggled out for the final check


def main():
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=1_000_000_000)
    ap.add_argument("--chunk", type=int, default=1 << 22)
    args = ap.parse_args()

    queries = make_queries()
    plans = engine.compile_patterns(queries)
    sc = StreamScanner(plans, args.chunk)
    step = sc.step_bytes

    # one plant straddling every 2nd window seam, alternating queries and
    # straddle phase so first-byte-left/last-byte-right seams both occur
    # (and both queries get planted even at the 16 MB CI smoke size)
    seam_starts = []
    w, si = 1, 0
    while w * step + 40 < args.size:
        qi = si % len(queries)
        phase = 1 + (si % (len(queries[qi]) - 1))
        seam_starts.append((w * step - phase, qi))
        w += 2
        si += 1

    t0 = time.perf_counter()
    counts = sc.count_many(corpus(args.size, queries, seam_starts))
    dt = time.perf_counter() - t0

    planted = corpus.planted
    order = sc.order  # engine rows are plan-concatenated
    ok = all(counts[r] == planted[order[r]] for r in range(len(counts)))
    gbps = args.size / dt / 1e9
    print(f"scanned {args.size / 1e6:.0f} MB in {dt:.2f}s  ({gbps:.3f} GB/s)")
    print(
        f"chunks: {sc.dispatch_count} x {sc.window_bytes} B window "
        f"(~{sc.device_bytes_per_chunk / 1e6:.1f} MB device working set; "
        f"resident index would need ~{9.5 * args.size / 1e9:.1f} GB)"
    )
    for r in range(len(counts)):
        qi = order[r]
        print(
            f"query {qi} (m={len(queries[qi])}): {int(counts[r])} hits, "
            f"{planted[qi]} planted (seam-straddling)"
        )
    if not ok:
        raise SystemExit("FAIL: streamed counts != planted occurrences")
    print("ok — exact across all window seams, O(chunk) device memory")


if __name__ == "__main__":
    main()
