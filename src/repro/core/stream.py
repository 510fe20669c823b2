"""Streaming scan engine: bounded-memory single-pass matching over unbounded
texts (DESIGN.md §9).

The resident engine (core/engine.py) wants the whole corpus on device —
``build_index`` materializes packed + block_fp for the full (B, n) batch,
4.5 bytes of device memory per byte of input (4 more while it builds).
That blocks the ROADMAP's grep/log-scan/pipeline-filter workloads the
moment a corpus outgrows the device.  This module answers the same
count/any/positions queries EXACTLY over arbitrarily long inputs in
O(chunk) device memory:

  * :class:`StreamScanner` re-chunks any byte source (bytes, arrays, files,
    iterables of chunks) into fixed-capacity windows, carries an
    ``overlap`` tail of ``max_m - 1`` bytes (rounded up to the EPSMc beta
    block so every window starts on a GLOBAL beta boundary — the
    block-phase carry) across windows, and issues exactly ONE jitted
    dispatch per chunk;

  * seam exactness is by END-position attribution: a window counts only the
    occurrences whose last byte falls in its newly-streamed region.  Any
    occurrence ending there started at most max_m - 1 bytes earlier, i.e.
    inside the carried overlap, so its full window is visible; occurrences
    ending inside the overlap were already counted by the previous window
    and are subtracted via a tiny (overlap-sized) prefix sub-index inside
    the same dispatch.  Each occurrence is therefore counted exactly once —
    no misses and no double counts at seams (invariants: DESIGN.md §9);

  * the host/device loop is double-buffered: chunk i+1 is ``device_put``
    while chunk i's dispatch computes (JAX dispatch is asynchronous), and
    the device-side count accumulator is a donated buffer on accelerator
    backends, so streaming adds no per-chunk sync and no growing state.

Approximate plans stream too: a <= k-mismatch occurrence spans the same m
bytes as an exact one, so the overlap/attribution argument is untouched and
``count_many(..., k=k)`` (relaxed gate and all) simply runs per chunk.

Two extensions ride on the same seam rule (DESIGN.md §10):

  * a scanner can start MID-stream: ``count_many/masks(..., prefix=, start=)``
    inject a carried overlap prefix and a global byte offset, so disjoint
    ranges of one logical stream can be scanned by different scanners (or
    hosts — core/shard_stream.py) and merged exactly, the shard boundary
    being just a second-level window seam;

  * sources may be gzip/zstd-compressed: wrap them in :class:`Compressed`
    and frames decompress incrementally into the same O(chunk) window
    (cold-storage corpora never materialize, and decompression overlaps
    device compute exactly like the host->device copy does).
"""

from __future__ import annotations

import functools
import logging
import time
from collections import deque
from typing import Iterator, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.engine import PatternPlan
from repro.core.epsm import EPSMC_BETA
from repro.obs.recorder import Recorder, logging_sink

_LOG = logging.getLogger("repro.stream")

# The module's default flight recorder: disabled (no spans, no fencing, no
# buffers, no profiler annotations) but with the module logger as an event
# sink, so the pre-recorder log lines (auto-chunk probe, kernel fallback,
# stragglers) keep appearing when no recorder is attached (DESIGN.md §13).
_DEFAULT_REC = Recorder(enabled=False, fence=False, sinks=(logging_sink(_LOG),))

# Floor device window capacity (bytes) for adaptive sizing, and the value a
# backend with no memory stats and negligible dispatch overhead lands on.
# ~4 MiB keeps per-chunk dispatch overhead amortized while the working set
# (window + packed + block_fp + fingerprint temporaries, ~9.5 bytes/byte)
# stays far below any device's memory.
DEFAULT_CHUNK_BYTES = 1 << 22
# Adaptive sizing bounds: never below 1 MiB (seam overhead dominates), never
# above 128 MiB (diminishing amortization, fast fallback compile times).
MIN_CHUNK_BYTES = 1 << 20
MAX_CHUNK_BYTES = 1 << 27
# read() granularity for file-like sources
_READ_BYTES = 1 << 20

_DISPATCH_OVERHEAD_S: Optional[float] = None


def _dispatch_overhead_s() -> float:
    """One-time measured per-dispatch overhead of this backend (seconds):
    the amortized cost of pushing one trivial jitted computation through the
    dispatch path.  Cached for the process — the probe is a few dozen tiny
    dispatches, microseconds each."""
    global _DISPATCH_OVERHEAD_S
    if _DISPATCH_OVERHEAD_S is None:
        f = jax.jit(lambda x: x + 1)
        x = jnp.zeros((8,), jnp.int32)
        f(x).block_until_ready()  # compile outside the timed region
        reps = 32
        t0 = time.perf_counter()
        for _ in range(reps):
            x = f(x)
        x.block_until_ready()
        _DISPATCH_OVERHEAD_S = (time.perf_counter() - t0) / reps
    return _DISPATCH_OVERHEAD_S


def auto_chunk_bytes(
    *,
    device=None,
    overhead_frac: float = 0.02,
    assumed_gbps: float = 1.0,
) -> int:
    """Adaptive chunk size: device memory budget + measured dispatch
    overhead, replacing the fixed 4 MiB default (DESIGN.md §11).

    Two constraints pick the size:

      * overhead floor — the one-time dispatch-overhead probe bounds the
        per-chunk fixed cost; the chunk must be big enough that this cost is
        <= ``overhead_frac`` of the chunk's scan time at a conservative
        ``assumed_gbps`` streaming rate;
      * memory ceiling — the streaming working set is ~9.5 device bytes per
        streamed byte (StreamScanner.device_bytes_per_chunk), so the chunk
        must keep that working set inside a fraction of the device's free
        memory (``memory_stats``; the CPU backend reports none and gets a
        512 MiB budget, any other backend that reports none raises).

    The result is clamped to [MIN_CHUNK_BYTES, MAX_CHUNK_BYTES] and rounded
    to the EPSMc beta block.
    """
    dev = device
    if dev is None:
        dev = jax.local_devices()[0]
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit")
    if limit:
        free = max(int(limit) - int(stats.get("bytes_in_use", 0)), limit // 8)
        budget = free // 4
    elif dev.platform == "cpu":
        budget = 512 << 20  # host-RAM-backed: no device limit to read
    else:
        raise RuntimeError(
            f"{dev.platform} device {dev} reports no memory limit "
            f"(memory_stats() = {stats!r}); pass chunk_bytes explicitly"
        )
    mem_cap = budget // 10  # ~9.5 working-set bytes per streamed byte
    floor = int(
        _dispatch_overhead_s() / overhead_frac * assumed_gbps * 1e9
    )
    chunk = max(DEFAULT_CHUNK_BYTES, floor)
    chunk = max(MIN_CHUNK_BYTES, min(chunk, mem_cap, MAX_CHUNK_BYTES))
    return _round_up(chunk, EPSMC_BETA)

def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


class Compressed:
    """Marks a byte source as gzip/zstd frames to decompress on the fly.

    ``source`` may be compressed bytes, a binary file-like, or an iterator
    of frames (e.g. one gzip member / zstd frame per cold-storage object) —
    concatenated frames are legal in both formats and decode as one logical
    stream.  ``codec`` is "gzip", "zstd", or "auto" (sniff the first frame's
    magic).  zstd needs the `zstandard` package; its absence raises only
    when a zstd source is actually opened."""

    def __init__(self, source, codec: str = "auto"):
        if codec not in ("auto", "gzip", "zstd"):
            raise ValueError(f"unknown codec {codec!r}")
        self.source = source
        self.codec = codec


def _raw_pieces(source) -> Iterator[bytes]:
    """COMPRESSED byte pieces of a Compressed source's underlying stream."""
    if isinstance(source, (bytes, bytearray, memoryview)):
        yield bytes(source)
        return
    if hasattr(source, "read"):
        while True:
            b = source.read(_READ_BYTES)
            if not b:
                return
            yield bytes(b)
        return
    for piece in source:
        if isinstance(piece, np.ndarray):
            piece = piece.tobytes()
        yield bytes(piece)


_GZIP_MAGIC = b"\x1f\x8b"
_ZSTD_MAGIC = b"\x28\xb5\x2f\xfd"


def _chain_head(head: bytes, rest) -> Iterator[bytes]:
    if head:
        yield head
    yield from rest


def _new_decompressor(codec: str):
    if codec == "gzip":
        import zlib

        return zlib.decompressobj(wbits=16 + zlib.MAX_WBITS)
    try:
        import zstandard
    except ImportError as e:  # gated dep: only zstd sources need it
        raise RuntimeError(
            "zstd-compressed sources need the `zstandard` package "
            "(pip install zstandard), which is not installed"
        ) from e
    return zstandard.ZstdDecompressor().decompressobj()


def _decompressed_chunks(c: Compressed) -> Iterator[np.ndarray]:
    """Incremental multi-frame decompression: O(compressed piece + emitted
    chunk) host memory, frames restarted via each decompressor's
    eof/unused_data contract (zlib and zstandard expose the same one)."""
    codec = c.codec
    d = None
    pieces = _raw_pieces(c.source)
    head = b""
    if codec == "auto":
        # a read()/iterator may legally deliver < 4 bytes: buffer until the
        # longest magic is decidable before sniffing
        for piece in pieces:
            head += piece
            if len(head) >= len(_ZSTD_MAGIC):
                break
        codec = "zstd" if head[: len(_ZSTD_MAGIC)] == _ZSTD_MAGIC else "gzip"
    for data in _chain_head(head, pieces):
        while data:
            if d is None:
                d = _new_decompressor(codec)
            out = d.decompress(data)
            if out:
                yield np.frombuffer(out, np.uint8)
            if d.eof:  # frame boundary: restart on the leftover bytes
                data = d.unused_data
                d = None
            else:
                data = b""
    if d is not None and not d.eof:
        raise ValueError(f"truncated {codec} stream")


def _as_chunks(source) -> Iterator[np.ndarray]:
    """Normalize any byte source into an iterator of host uint8 arrays."""
    if isinstance(source, Compressed):
        yield from _decompressed_chunks(source)
        return
    if isinstance(source, str):
        source = source.encode("utf-8", errors="surrogateescape")
    if isinstance(source, (bytes, bytearray, memoryview)):
        yield np.frombuffer(bytes(source), np.uint8)
        return
    if isinstance(source, np.ndarray):
        a = source.reshape(-1)
        yield a if a.dtype == np.uint8 else a.astype(np.uint8)
        return
    if isinstance(source, jax.Array):
        yield np.asarray(jax.device_get(source)).astype(np.uint8).reshape(-1)
        return
    if hasattr(source, "read"):
        while True:
            b = source.read(_READ_BYTES)
            if not b:
                return
            yield np.frombuffer(bytes(b), np.uint8)
    else:
        for piece in source:
            yield from _as_chunks(piece)


@functools.lru_cache(maxsize=None)
def _jitted_count_step(fused: bool, shared: bool = True):
    """Jit the chunk step lazily: donating the count accumulator lets XLA
    reuse its buffer across chunks on accelerator backends (CPU ignores
    donation and warns, so it is gated on the backend) — and the backend
    query must NOT run at import time, or merely importing repro.core would
    initialize XLA before the user can configure it."""
    donate = (0,) if jax.default_backend() != "cpu" else ()
    step = _fused_count_step if fused else _count_step
    return functools.partial(
        jax.jit, static_argnames=("ov", "k", "shared"), donate_argnums=donate
    )(functools.partial(step, shared=shared))


def _fused_count_step(
    counts, window, length, prev_ov, plans, *, ov: int, k, shared: bool = True
):
    """One streaming chunk, seam correction FUSED into the scan: the
    ``end_min=prev_ov`` gate inside every matcher keeps exactly the
    occurrences whose END falls in the newly-streamed region, replacing the
    reference path's separate overlap-prefix subtraction (DESIGN.md §11
    proves the two produce identical integers).  One count_many — i.e. one
    fingerprint-bank pass and one shared compaction — per chunk.  Its
    device operations carry their stage in ``op_name``: ``index``, then
    ``count`` with the engine's own stages inside, then ``accumulate``."""
    del ov  # the fused gate needs no prefix sub-index
    idx = engine.build_index(window[None, :], jnp.asarray(length)[None])
    with jax.named_scope("count"):
        c = engine.count_many(idx, plans, k=k, end_min=prev_ov, shared=shared)
    with jax.named_scope("accumulate"):
        return counts + c[0]


def _count_step(
    counts, window, length, prev_ov, plans, *, ov: int, k, shared: bool = True
):
    """Reference two-pass chunk step: full-window counts minus
    overlap-prefix counts.  Kept as the fallback and the oracle the fused
    paths (``_fused_count_step`` and the megascan kernel) are pinned
    against in tests/test_stream.py and tests/test_megascan.py.

    ``window`` is (N,) uint8 with ``length`` valid bytes, the first
    ``prev_ov`` of which were carried from the previous window (0 for the
    first chunk).  The subtraction removes exactly the occurrences whose
    window lies entirely inside the carried prefix — the ones the previous
    chunk already counted — so the sum over chunks is the whole-text count.
    The prefix sub-index spans ``ov`` (static, <= max_m + beta - 2) bytes:
    its cost is noise next to the O(N) window scan, and both run in this one
    dispatch."""
    idx = engine.build_index(window[None, :], jnp.asarray(length)[None])
    with jax.named_scope("count"):
        c = engine.count_many(idx, plans, k=k, shared=shared)
    if ov:
        with jax.named_scope("seam_gate"):
            pre_idx = engine.build_index(
                window[None, :ov],
                jnp.minimum(jnp.asarray(prev_ov), length)[None],
            )
            c = c - engine.count_many(pre_idx, plans, k=k, shared=shared)
    with jax.named_scope("accumulate"):
        return counts + c[0]


@functools.lru_cache(maxsize=None)
def _jitted_kernel_step(spec):
    """Chunk step through the fused Pallas megakernel (kernels/megascan):
    ONE pallas dispatch stages each tile once and answers every group, the
    k-mismatch accumulator, and the seam gate together.  ``spec`` is the
    static MegaSpec; the (length, prev_ov) scalars are traced operands, so
    one compilation serves every chunk."""
    from repro.kernels.megascan import megascan_count_window

    def step(counts, window, length, prev_ov, plans):
        return counts + megascan_count_window(
            window, plans, spec, length=length, prev_ov=prev_ov
        )

    donate = (0,) if jax.default_backend() != "cpu" else ()
    return jax.jit(step, donate_argnums=donate)


@functools.partial(jax.jit, static_argnames=("k", "fused"))
def _mask_step(window, length, prev_ov, plans, *, k, fused: bool = True):
    """(P_total, N) bool match-start mask for one chunk, de-duplicated at the
    seam: a start survives iff its occurrence ENDS at or past ``prev_ov``
    (ends inside the carried prefix belong to the previous chunk).  The
    fused form pushes that gate into the matchers' candidate masks
    (``end_min``); the reference form post-filters — bit-identical.  Its
    device operations carry ``index``, then ``match`` with the engine's own
    stages inside (and the reference form's ``seam_gate``) in ``op_name``."""
    idx = engine.build_index(window[None, :], jnp.asarray(length)[None])
    if fused:
        with jax.named_scope("match"):
            return engine.match_many(idx, plans, k=k, end_min=prev_ov)[0]
    with jax.named_scope("match"):
        mask = engine.match_many(idx, plans, k=k)[0]
    with jax.named_scope("seam_gate"):
        pos = jnp.arange(window.shape[0], dtype=jnp.int32)
        keeps = []
        for plan in plans:
            keep = pos + (plan.m - 1) >= prev_ov
            keeps.append(jnp.broadcast_to(
                keep[None, :], (plan.n_patterns, window.shape[0])
            ))
        return mask & jnp.concatenate(keeps, axis=0)


class StreamScanner:
    """Chunked, double-buffered, exact streaming matcher for a plan set.

    Device memory is O(chunk_bytes) regardless of input length; every chunk
    costs exactly one jitted dispatch (``dispatch_count`` audits this).
    Pattern rows are in plan-concatenated order, as everywhere in the
    engine; ``order`` maps them back to the original pattern sequence.

    ``k`` overrides the per-plan mismatch budget exactly like
    ``engine.count_many(..., k=)``; None runs each plan at the budget it was
    compiled for.

    ``chunk_bytes`` may be an int or ``"auto"`` (the default): auto picks
    the window from the device memory budget and a one-time measured
    dispatch-overhead probe (:func:`auto_chunk_bytes`) and logs the chosen
    value; the resolved size is ``self.chunk_bytes``.

    ``fused`` (default True) runs each chunk with the seam correction fused
    into the matchers (``count_many(..., end_min=prev_ov)`` — one scan, no
    overlap-prefix sub-index); False keeps the reference two-pass step,
    bit-identical by DESIGN.md §11.  ``use_kernel`` additionally routes
    counting through the fused Pallas megakernel (kernels/megascan) when
    the plan set is kernel-eligible — ineligible sets fall back to the
    pure-JAX fused path (logged), never to different results.

    ``device`` pins every dispatch (windows, accumulator, plan state) to one
    local device; the sharded scanner (core/shard_stream.py) uses this to
    fan shards out over the fleet's devices, whose async dispatch queues
    then drain concurrently.  None keeps jax's default placement.

    ``count_many``/``masks``/``positions_many`` accept ``prefix``/``start``
    to scan a mid-stream RANGE of a larger logical stream: ``start`` is the
    global byte offset of the source's first byte and ``prefix`` the up-to-
    ``overlap`` bytes immediately before it (its occurrences-ending-inside
    belong to whoever scanned the preceding range — the shard seam is just
    a second-level window seam, DESIGN.md §10).  ``start - len(prefix)``
    must sit on a beta block boundary so chunk-local aligned block
    fingerprints still coincide with the global ones.

    ``recorder`` attaches a :class:`~repro.obs.recorder.Recorder` (DESIGN.md
    §13): every chunk then traces a ``host_prep`` span (source read /
    decompress / window assembly), a ``device_put`` span, and a fenced
    ``dispatch`` span (the jitted scan, seam fusion included), plus the
    ``bytes_scanned`` (new stream bytes) and ``put_bytes`` (window bytes
    sent to the device) counters; the mask path adds ``wait`` (the host's
    block on the mask step), ``fetch`` (the mask's copy to the host, its
    bytes counted in ``fetch_bytes``) and ``positions`` (the mask's rows to
    positions) spans, and ``count_many`` one ``fetch`` of its counts.
    Every span of one chunk carries the same ``chunk`` id.  The default is
    the module's disabled recorder — no spans, no fencing, the
    double-buffered pipeline untouched — whose only effect is feeding
    instant events (auto-chunk probe, kernel fallback, stragglers) to the
    module logger.  ``lane`` names this scanner's trace track (the sharded
    scanner sets it).

    ``watchdog`` arms a :class:`~repro.dist.fault_tolerance.StepWatchdog`
    around every chunk's HOST step — source read, decompression, window
    assembly — the part where a slow disk or object store stalls (device
    dispatch is asynchronous and surfaces at the final sync, not here).  ``policy="raise"`` turns a stalled chunk into a
    ``StragglerAbort`` a supervisor can act on; ``on_straggler(event)``
    observes flagged chunks under the non-raising policies (the elastic
    sharded scanner sheds a straggling shard's trailing range there,
    DESIGN.md §12).
    """

    def __init__(
        self,
        plans: Sequence[PatternPlan],
        chunk_bytes: Union[int, str] = "auto",
        *,
        k: Optional[int] = None,
        device=None,
        fused: bool = True,
        shared: bool = True,
        use_kernel: bool = False,
        watchdog=None,
        on_straggler=None,
        recorder: Optional[Recorder] = None,
        lane: Optional[str] = None,
    ):
        self.plans = tuple(plans)
        if not self.plans:
            raise ValueError("StreamScanner needs at least one PatternPlan")
        # rec is consulted unconditionally on every chunk (spans + counters);
        # the module default is the disabled recorder with a logging sink
        # (DESIGN.md §13).  ``lane`` names this scanner's trace track — the
        # sharded scanner sets it so stolen ranges stay attributed.
        self.rec = _DEFAULT_REC if recorder is None else recorder
        self.lane = lane
        self.device = device
        if device is not None:
            self.plans = engine.replicate_plans(self.plans, device)
        self.k = k
        self.fused = bool(fused)
        # shared=False pins the pre-fusion per-group engine path (each group
        # pays its own fingerprint pass + compaction — count_many shared=False);
        # the megascan benchmark's per-group baseline.
        self.shared = bool(shared)
        self.spec = None
        if use_kernel:
            from repro.kernels.megascan import build_mega_spec

            self.spec = build_mega_spec(self.plans, k=k)
            if self.spec is None:
                self.rec.event(
                    "kernel_fallback", lane=self.lane,
                    reason="megascan ineligible for this plan set; "
                    "using the pure-JAX fused path",
                )
        if chunk_bytes == "auto":
            chunk_bytes = auto_chunk_bytes(device=device)
            self.rec.event(
                "auto_chunk", lane=self.lane, chunk_bytes=int(chunk_bytes),
                dispatch_overhead_us=round(1e6 * _dispatch_overhead_s(), 1),
            )
        self.chunk_bytes = int(chunk_bytes)
        self.max_m = max(p.m for p in self.plans)
        # overlap >= max_m - 1 carries every possibly-straddling occurrence
        # start; rounding up to the beta block keeps each window's start on
        # a global beta boundary, so chunk-local aligned block fingerprints
        # coincide with the global ones (EPSMc block-phase carry).
        self.overlap = _round_up(self.max_m - 1, EPSMC_BETA)
        window = max(self.chunk_bytes, self.overlap + EPSMC_BETA)
        self.window_bytes = _round_up(window, EPSMC_BETA)
        self.step_bytes = self.window_bytes - self.overlap
        self.n_patterns = sum(p.n_patterns for p in self.plans)
        self.order = engine.plan_order(self.plans)
        self.dispatch_count = 0
        self._chunks = 0  # windows produced: the next window's chunk id
        self.watchdog = watchdog
        self.on_straggler = on_straggler

    # -- host-side re-chunking ---------------------------------------------

    def _injection(self, prefix, start: int) -> Tuple[np.ndarray, int]:
        """Validate a mid-stream (prefix, start) injection; returns the
        normalized carry array and the global position of the first window."""
        if prefix is None:
            carry = np.zeros(0, np.uint8)
        else:
            carry = np.ascontiguousarray(
                np.asarray(jax.device_get(prefix)).reshape(-1), np.uint8
            )
        if len(carry) > self.overlap:
            raise ValueError(
                f"injected prefix ({len(carry)} B) exceeds the scanner "
                f"overlap ({self.overlap} B)"
            )
        base = int(start) - len(carry)
        if base % EPSMC_BETA:
            raise ValueError(
                "start - len(prefix) must be a multiple of EPSMC_BETA "
                f"({EPSMC_BETA}) to preserve the global block phase; got "
                f"start={start}, len(prefix)={len(carry)}"
            )
        return carry, base

    def _windows(
        self, source, *, prefix=None, start: int = 0
    ) -> Iterator[Tuple[np.ndarray, int, int, int, bool]]:
        """Yield (window (N,) uint8, valid_len, carry_len, base, last):
        fixed-capacity host windows where window[:carry_len] re-feeds the
        previous window's tail and ``base`` is the global position of
        window[0].  ``last`` says the source has ended and this window took
        its final bytes (a source that ends exactly on a window's edge is
        found out only by the next read).  ``prefix``/``start`` seed the
        first window's carry for mid-stream ranges (the first chunk's seam
        subtraction then removes occurrences the preceding range already
        owned)."""
        N, ov = self.window_bytes, self.overlap
        pieces: deque = deque()
        have = 0
        carry, base = self._injection(prefix, start)
        exhausted = False
        it = _as_chunks(source)
        while True:
            while not exhausted and have < N - len(carry):
                try:
                    piece = next(it)
                except StopIteration:
                    exhausted = True
                    break
                if len(piece):
                    pieces.append(piece)
                    have += len(piece)
            new_len = min(have, N - len(carry))
            if new_len == 0:
                return  # nothing newly streamed: no window to emit
            win = np.zeros(N, np.uint8)
            win[: len(carry)] = carry
            filled = len(carry)
            need = new_len
            while need:
                piece = pieces.popleft()
                take = min(len(piece), need)
                win[filled : filled + take] = piece[:take]
                if take < len(piece):
                    pieces.appendleft(piece[take:])
                filled += take
                need -= take
            have -= new_len
            L = len(carry) + new_len
            yield win, L, len(carry), base, exhausted and not have
            carry = win[max(0, L - ov) : L].copy() if ov else carry
            base += L - len(carry)

    def _steps(self, source, *, prefix=None, start: int = 0):
        """The `_windows` iterator with each window's PRODUCTION (source
        read, decompress, assembly) wrapped in a ``host_prep`` recorder span
        and, when a watchdog is armed, timed for straggling: the stall site
        for slow storage.  A flagged chunk either raises (policy="raise") or
        is recorded as a ``straggler`` event and reported to
        ``on_straggler``.

        Yields (chunk, window, valid_len, carry_len, base).  ``chunk`` is
        the window's id, counted over the scanner's life: every span of
        the window's work (``host_prep``, ``device_put``, ``dispatch``,
        ``wait``, ``fetch``, ``positions``) carries it."""
        rec, lane = self.rec, self.lane
        wd = self.watchdog
        it = self._windows(source, prefix=prefix, start=start)
        step = 0
        last = False
        while not last:
            chunk = self._chunks
            if wd is not None:
                wd.start_step(step)
            try:
                with rec.span("host_prep", lane=lane, chunk=chunk) as sp:
                    win, L, carry_len, base, last = next(it)
                    sp.set(bytes=int(L) - int(carry_len))
            except StopIteration:
                if wd is not None:
                    wd.end_step()  # close the pair; an instant EOF never flags
                return
            if wd is not None and wd.end_step() is not None:
                ev = wd.events[-1]
                rec.event(
                    "straggler", lane=lane, step=ev.step,
                    duration_s=round(ev.duration_s, 6),
                    median_s=round(ev.median_s, 6),
                    factor=round(ev.factor, 2),
                )
                if self.on_straggler is not None:
                    self.on_straggler(ev)
            step += 1
            self._chunks += 1
            yield chunk, win, L, carry_len, base

    # -- device loop --------------------------------------------------------

    def _put(self, chunk, win):
        """Host->device window transfer under a ``device_put`` span, its
        bytes counted in ``put_bytes``.  The transfer itself is async; the
        fence (enabled recorder only) charges the copy to this span instead
        of the next dispatch."""
        self.rec.count("put_bytes", int(win.nbytes))
        with self.rec.span(
            "device_put", lane=self.lane, chunk=chunk, bytes=int(win.nbytes)
        ) as sp:
            return sp.fence(jax.device_put(win, self.device))

    def _dispatch_count(self, counts, chunk, window_dev, length, prev_ov):
        self.dispatch_count += 1
        new_bytes = int(length) - int(prev_ov)
        with self.rec.span(
            "dispatch", lane=self.lane, chunk=chunk, bytes=new_bytes,
        ) as sp:
            if self.spec is not None:
                counts = _jitted_kernel_step(self.spec)(
                    counts, window_dev, length, prev_ov, self.plans
                )
            else:
                counts = _jitted_count_step(self.fused, self.shared)(
                    counts, window_dev, length, prev_ov, self.plans,
                    ov=self.overlap, k=self.k,
                )
            # seam fusion (end_min gate / overlap sub-index) runs inside this
            # same dispatch; the fence makes the span cover the device work
            sp.fence(counts)
        self.rec.count("bytes_scanned", new_bytes)
        return counts

    def _zero_counts(self):
        z = jnp.zeros((self.n_patterns,), jnp.int32)
        return z if self.device is None else jax.device_put(z, self.device)

    def count_device(self, source, *, prefix=None, start: int = 0):
        """Device-resident (P_total,) int32 count accumulator, NOT synced —
        the sharded scanner enqueues every shard's chunks this way and pays
        one collective merge instead of a per-shard host round-trip.

        Double-buffered: the (i+1)-th window's host->device transfer is
        issued before the i-th window's (asynchronously dispatched) compute
        is consumed, and nothing here waits on device results at all."""
        counts = self._zero_counts()
        pending = None
        for chunk, win, L, carry_len, _base in self._steps(
            source, prefix=prefix, start=start
        ):
            dev = self._put(chunk, win)
            if pending is not None:
                counts = self._dispatch_count(counts, *pending)
            pending = (chunk, dev, np.int32(L), np.int32(carry_len))
        if pending is not None:
            counts = self._dispatch_count(counts, *pending)
        return counts

    def count_many(self, source, *, prefix=None, start: int = 0) -> np.ndarray:
        """int32 (P_total,) exact occurrence counts over the whole stream
        (or, with ``prefix``/``start``, over one mid-stream range — counting
        exactly the occurrences whose END lies inside it)."""
        counts = self.count_device(source, prefix=prefix, start=start)
        return self._fetch(counts)

    def any_many(self, source) -> np.ndarray:
        """bool (P_total,) — does each pattern occur anywhere in the stream?"""
        return self.count_many(source) > 0

    def contains_any(self, source, *, sync_every: int = 8) -> bool:
        """Scalar verdict with early exit: the accumulator is polled every
        ``sync_every`` chunks so a hit near the head of a long stream stops
        the scan without draining the source."""
        counts = self._zero_counts()
        pending = None
        chunks = 0
        for chunk, win, L, carry_len, _base in self._steps(source):
            dev = self._put(chunk, win)
            if pending is not None:
                counts = self._dispatch_count(counts, *pending)
                chunks += 1
                if chunks % sync_every == 0 and bool(counts.sum() > 0):
                    return True
            pending = (chunk, dev, np.int32(L), np.int32(carry_len))
        if pending is not None:
            counts = self._dispatch_count(counts, *pending)
        return bool(np.asarray(jax.device_get(counts)).sum() > 0)

    def masks(
        self, source, *, prefix=None, start: int = 0
    ) -> Iterator[Tuple[int, int, np.ndarray]]:
        """Yield (base, new_start, (P_total, L) bool) per chunk: the seam-
        deduped match-start mask of the chunk's valid bytes.  A start at
        column j is global position base + j; every occurrence appears in
        exactly one yielded mask.  ``new_start`` is the carried-prefix
        length (starts before new_start - max_m + 1 are always False).
        With ``prefix``/``start``, bases are global stream positions and
        occurrences ending before ``start`` are dropped (previous range's)."""
        for _chunk, out in self._masks(source, prefix=prefix, start=start):
            yield out

    def _masks(self, source, *, prefix, start: int):
        """``masks``, each mask paired with its chunk id."""
        pending = None
        for chunk, win, L, carry_len, base in self._steps(
            source, prefix=prefix, start=start
        ):
            dev = self._put(chunk, win)
            if pending is not None:
                yield pending[0], self._flush_mask(*pending)
            pending = (chunk, dev, np.int32(L), np.int32(carry_len), base, L)
        if pending is not None:
            yield pending[0], self._flush_mask(*pending)

    def _flush_mask(self, chunk, dev, length, prev_ov, base, L):
        """Dispatch one chunk's mask step and bring its mask to the host.
        Traced, the host's block on the step is the ``wait`` span and the
        copy alone the ``fetch`` span; untraced, the copy syncs by itself."""
        self.dispatch_count += 1
        new_bytes = int(length) - int(prev_ov)
        rec, lane = self.rec, self.lane
        with rec.span(
            "dispatch", lane=lane, chunk=chunk, bytes=new_bytes,
        ) as sp:
            mask = sp.fence(_mask_step(
                dev, length, prev_ov, self.plans, k=self.k, fused=self.fused
            ))
        rec.count("bytes_scanned", new_bytes)
        with rec.span("wait", lane=lane, chunk=chunk) as sp:
            sp.sync(mask)
        return base, int(prev_ov), self._fetch(mask, chunk)[:, :L]

    def _fetch(self, value, chunk=None):
        """Device->host copy of ``value`` under a ``fetch`` span, its bytes
        counted in ``fetch_bytes``."""
        with self.rec.span("fetch", lane=self.lane, chunk=chunk):
            out = np.asarray(jax.device_get(value))
        self.rec.count("fetch_bytes", out.nbytes)
        return out

    def positions_many(
        self, source, *, prefix=None, start: int = 0
    ) -> List[np.ndarray]:
        """Per-pattern sorted global occurrence start positions (host side;
        output-sized host memory, still O(chunk) device memory).  Each
        chunk's extraction from its mask is a ``positions`` span."""
        out: List[List[np.ndarray]] = [[] for _ in range(self.n_patterns)]
        for chunk, (base, _new_start, mask) in self._masks(
            source, prefix=prefix, start=start
        ):
            with self.rec.span("positions", lane=self.lane, chunk=chunk):
                for p_i in range(self.n_patterns):
                    (loc,) = np.nonzero(mask[p_i])
                    if len(loc):
                        out[p_i].append(loc.astype(np.int64) + base)
        return [
            np.concatenate(o) if o else np.zeros(0, np.int64) for o in out
        ]

    # -- accounting ---------------------------------------------------------

    @property
    def device_bytes_per_chunk(self) -> int:
        """Estimated peak device working set per chunk: window text (1) +
        packed u32 view (4) + block fingerprints (0.5) + one fingerprint
        temporary (4) per byte, plus the plan LUTs."""
        per_byte = self.window_bytes + self.overlap
        luts = 0
        for p in self.plans:
            luts += (1 << p.kbits)  # lut_any
            if p.lut_pid is not None:
                luts += 4 * (1 << p.kbits)
            if p.lut_bits is not None:
                luts += 4 * p.lut_bits.shape[-1] * (1 << p.kbits)
            if p.relaxed_lut is not None:
                luts += (1 << p.kbits)
        return int(9.5 * per_byte) + luts


# ---------------------------------------------------------------------------
# Convenience wrappers (the epsm.find/count stream= escape hatch lands here)
# ---------------------------------------------------------------------------

def stream_count(
    source,
    patterns: Sequence,
    *,
    k: int = 0,
    chunk_bytes: Union[int, str] = "auto",
    use_kernel: bool = False,
) -> np.ndarray:
    """int32 (P,) exact (or <= k-mismatch) counts in ORIGINAL pattern order.
    ``chunk_bytes="auto"`` (default) sizes the window adaptively."""
    plans = engine.compile_patterns_cached(list(patterns), k=k)
    sc = StreamScanner(plans, chunk_bytes, k=k, use_kernel=use_kernel)
    counts = sc.count_many(source)
    out = np.zeros_like(counts)
    out[sc.order] = counts
    return out


def find_stream(
    source,
    pattern,
    *,
    k: int = 0,
    chunk_bytes: Union[int, str] = "auto",
) -> np.ndarray:
    """Whole-stream bool match-start mask for ONE pattern, assembled on the
    host chunk by chunk (host memory is O(n); device stays O(chunk))."""
    plans = engine.compile_patterns_cached([pattern], k=k)
    sc = StreamScanner(plans, chunk_bytes, k=k)
    out = np.zeros(sc.window_bytes, bool)
    n = 0
    for base, _new_start, mask in sc.masks(source):
        end = base + mask.shape[1]
        if end > len(out):
            out = np.resize(out, max(2 * len(out), end))
            out[n:] = False
        out[base:end] |= mask[0]
        n = max(n, end)
    return out[:n]
