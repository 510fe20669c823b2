"""Traffic kind ``stream_scan``: one scan job over an unbounded log stream.

The stream cycles a host buffer of HDFS-style log text, generated from the
seed in set-up, for ``--seconds``: the scanner reads it piece by piece, and
the window closes when the source stops yielding and the job's answer has
reached the host.  The buffer ends on a newline and no query holds one, so
no occurrence spans the point where the buffer starts again; the expected
answer over the stream is then the reference's answer over the buffer,
repeated for every whole cycle, plus its answer over the last partial one.

Parameters (the workload file's ``traffic``): ``mode`` is ``count`` (one
``StreamScanner.count_many`` for the whole window) or ``locate`` (one
``positions_many``: every match position, as ``grep -b -o`` gives).  The
configuration gives the buffer size, the chunk size and the queries.

``control`` (the correctness control, not the benchmark) runs the same
window through the scanner's k = 1 path, which breaks the configuration's
k = 0 guarantee.
"""

from __future__ import annotations

import time

import numpy as np

from bench import harness, reference
from bench.traffic.hdfs_logs import hdfs_log_text
from repro.core import engine
from repro.core.stream import StreamScanner
from repro.obs.recorder import Recorder

WARM_CHUNKS = 3


class CycledSource:
    """Pieces of ``buf``, cycled from its start, until ``deadline`` (a
    ``time.monotonic`` reading) passes or ``limit`` bytes have gone."""

    def __init__(self, buf: np.ndarray, piece: int, *, deadline=None,
                 limit=None, span=None):
        self.buf, self.piece = buf, int(piece)
        self.deadline, self.limit = deadline, limit
        self.span = span
        self.bytes = 0

    def __iter__(self):
        n, pos = self.buf.size, 0
        while True:
            if self.deadline is not None and time.monotonic() >= self.deadline:
                return
            if self.limit is not None and self.bytes >= self.limit:
                return
            take = min(self.piece, n - pos)
            if self.span is not None:
                with self.span("source"):
                    out = self.buf[pos : pos + take]
            else:
                out = self.buf[pos : pos + take]
            pos = (pos + take) % n
            self.bytes += take
            yield out


def expected(ref_positions, m: int, n: int, total: int):
    """Positions over ``total`` bytes of the buffer cycled: every whole
    cycle, then the occurrences that fit in the partial one."""
    cycles, rest = divmod(total, n)
    parts = [ref_positions + c * n for c in range(cycles)]
    parts.append(ref_positions[ref_positions + m <= rest] + cycles * n)
    return np.concatenate(parts)


def scanner(cfg: dict, *, control: bool, recorder=None) -> StreamScanner:
    queries = [q.encode() for q in cfg["queries"]]
    k = 1 if control else 0
    plans = engine.compile_patterns_cached(queries, k=k)
    return StreamScanner(plans, int(cfg["chunk_bytes"]), k=k,
                         use_kernel=False, recorder=recorder)


def scan(sc: StreamScanner, mode: str, source) -> list:
    """Answers in query order: counts, or position arrays."""
    if mode == "count":
        rows = list(sc.count_many(source))
    else:
        rows = sc.positions_many(source)
    out = [None] * len(rows)
    for r, q in enumerate(sc.order):
        out[q] = rows[r]
    return out


def wrong_answers(got, buf: np.ndarray, queries, total: int, mode: str) -> int:
    ref = reference.find_all(buf, queries)
    wrong = 0
    for g, r, q in zip(got, ref, queries):
        want = expected(r, len(q), buf.size, total)
        ok = g == want.size if mode == "count" else np.array_equal(g, want)
        wrong += int(not ok)
    return wrong


def run(run: harness.Run, *, control: bool = False) -> harness.Outcome:
    cfg, mode = run.config, run.params["mode"]
    queries = [q.encode() for q in cfg["queries"]]
    buf = hdfs_log_text(int(cfg["buffer_bytes"]), run.seed)
    chunk = int(cfg["chunk_bytes"])
    # every shape the window uses: its chunk step is one program, whatever
    # the stream's length
    warm = scanner(cfg, control=control)
    scan(warm, mode, CycledSource(buf, chunk, limit=WARM_CHUNKS * chunk))
    rec = Recorder(enabled=True, fence=False) if run.trace else None
    sc = scanner(cfg, control=control, recorder=rec)
    t0 = time.monotonic()
    source = CycledSource(buf, chunk, deadline=t0 + run.seconds,
                          span=run.span)
    with run.window():
        got = scan(sc, mode, source)
        t1 = time.monotonic()
    peak = run.memory_peak()
    setup_s = t0 - run.t_start
    window_s = t1 - t0
    dispatches = sc.dispatch_count
    del sc, warm
    harness.log(f"{mode}: {source.bytes} B in {window_s:.3f} s, "
                f"{dispatches} chunks of {chunk} B, set-up {setup_s:.3f} s, "
                f"window compiles {run.window_compiles}")
    t_ref = time.monotonic()
    wrong = wrong_answers(got, buf, queries, source.bytes, mode)
    harness.log(f"reference checked {len(queries)} queries over "
                f"{source.bytes} B in {time.monotonic() - t_ref:.2f} s")
    gbps = source.bytes / window_s / 1e9
    layer = {"bytes": source.bytes, "dispatches": dispatches,
             "recorder": rec.summary() if rec is not None else None}
    return harness.Outcome(
        correct=wrong == 0, attempted=len(queries), failed=wrong,
        metrics={f"{mode}_scan_gbps": gbps, "setup_s": setup_s},
        checks={"wrong_answers": (wrong, 0)}, layer=layer,
        memory_peak_bytes=peak,
    )
