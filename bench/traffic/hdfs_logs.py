"""HDFS-style log text from a seed, after Loghub's HDFS_v1 data set.

A line is ``<yymmdd> <hhmmss> <thread> <level> <component>: <message>\\n``,
as in HDFS_v1 (Zhu et al., "Loghub", ISSRE 2023).  The message templates,
their weights and the field ranges are listed in each configuration file
under ``assumed``; the rare error templates that hold ``timeout!``,
``segfault`` and ``too many open files (24)`` are not in HDFS_v1 and are
marked so there.

Every line is rendered with fields of its own: its time, thread, addresses,
sizes and block id.  Block ids keep HDFS_v1's cardinality, 575,061 ids over
11,175,629 lines (19.43 lines per id): each assembly block of text draws
its own ids, one per LINES_PER_BLOCK_ID lines, and its lines take them up
at random, so an id recurs across its block's life (receive, respond,
store, delete) close together, as in the real log.

Generation is vectorised: a block's lines are a sequence of segments, each
a literal of the template or the decimal digits of one field, gathered from
one byte pool with a single numpy gather, on several threads.  Every block
is drawn from ``(seed, block index)`` alone, so the same seed gives the
same bytes whatever the thread count.
"""

from __future__ import annotations

import string
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# (weight, level, component, message); {blk} {ip} {ip2} {port} {size} {n}
# {sub} {job} {task} are filled per line.
TEMPLATES = (
    (17.0, "INFO", "dfs.DataNode$DataXceiver",
     "Receiving block {blk} src: /{ip}:{port} dest: /{ip2}:50010"),
    (15.0, "INFO", "dfs.DataNode$PacketResponder",
     "PacketResponder {n} for block {blk} terminating"),
    (15.0, "INFO", "dfs.DataNode$PacketResponder",
     "Received block {blk} of size {size} from /{ip}"),
    (15.0, "INFO", "dfs.FSNamesystem",
     "BLOCK* NameSystem.addStoredBlock: blockMap updated: {ip}:50010 is "
     "added to {blk} size {size}"),
    (5.0, "INFO", "dfs.FSNamesystem",
     "BLOCK* NameSystem.allocateBlock: /user/root/rand/_temporary/"
     "_task_{job}_m_{task}_0/part-{task}. {blk}"),
    (9.0, "INFO", "dfs.FSNamesystem",
     "BLOCK* NameSystem.delete: {blk} is added to invalidSet of {ip}:50010"),
    (9.0, "INFO", "dfs.FSDataset",
     "Deleting block {blk} file /mnt/hadoop/dfs/data/current/subdir{sub}/"
     "{blk}"),
    (4.0, "INFO", "dfs.DataNode$DataXceiver",
     "{ip}:50010 Served block {blk} to /{ip2}"),
    (3.0, "INFO", "dfs.DataNode$DataTransfer",
     "{ip}:50010:Transmitted block {blk} to /{ip2}:50010"),
    (2.0, "INFO", "dfs.DataBlockScanner", "Verification succeeded for {blk}"),
    (1.5, "INFO", "dfs.FSNamesystem",
     "BLOCK* ask {ip}:50010 to replicate {blk} to datanode(s) {ip2}:50010"),
    (1.0, "WARN", "dfs.DataNode$DataXceiver",
     "{ip}:50010:Got exception while serving {blk} to /{ip2}:"),
    (0.8, "INFO", "dfs.FSNamesystem",
     "BLOCK* NameSystem.addStoredBlock: Redundant addStoredBlock request "
     "received for {blk} on {ip}:50010 size {size}"),
    (0.5, "WARN", "dfs.DataNode$DataXceiver",
     "writeBlock {blk} received exception java.io.IOException: Could not "
     "read from stream"),
    (0.3, "WARN", "dfs.DataNode$PacketResponder",
     "IOException in BlockReceiver.run(): java.io.IOException: Connection "
     "reset by peer"),
    # not in HDFS_v1: rare errors that carry fixed tokens the scan queries
    (0.2, "WARN", "dfs.DataNode$DataXceiver",
     "writeBlock {blk} received exception java.net.SocketTimeoutException: "
     "read timeout! after 60000 ms"),
    (0.1, "ERROR", "dfs.DataNode",
     "native crc32 worker segfault at {size} in libhadoop.so"),
    (0.1, "WARN", "dfs.DataNode$DataXceiver",
     "DataXceiver for {blk} failed: java.io.IOException: too many open "
     "files (24)"),
)

# HDFS_v1 (Loghub): 11,175,629 lines, 575,061 distinct block ids
LINES_PER_BLOCK_ID = 11_175_629 / 575_061
BLOCK_BYTES = 1 << 24     # assembly block: whole lines, at most this long
MAX_THREADS = 8

# A numeric slot: (digits, zero-padded width or 0 for none).  A line's
# fields are made of slots and literals; "blk" and the addresses take
# several slots each.
SLOTS = {
    "day": (6, 6), "hms": (6, 6), "thread": (4, 0),
    "blk_sign": (1, 0), "blk": (19, 0),
    "ip_net": (1, 0), "ip_a": (3, 0), "ip_b": (3, 0),
    "ip2_net": (1, 0), "ip2_a": (3, 0), "ip2_b": (3, 0),
    "port": (5, 0), "size": (8, 0), "n": (1, 0), "sub": (2, 0),
    "job": (12, 0), "job_n": (4, 4), "task": (6, 6),
}
FIELDS = {  # a template field as a sequence of literals and slots
    "blk": ["blk_", ("blk_sign",), ("blk",)],
    "ip": ["10.25", ("ip_net",), ".", ("ip_a",), ".", ("ip_b",)],
    "ip2": ["10.25", ("ip2_net",), ".", ("ip2_a",), ".", ("ip2_b",)],
    "job": [("job",), "_", ("job_n",)],
    "port": [("port",)], "size": [("size",)], "n": [("n",)],
    "sub": [("sub",)], "task": [("task",)],
}
HEAD = [("day",), " ", ("hms",), " ", ("thread",), " "]


def _tokens(level: str, comp: str, msg: str) -> list:
    """A template's line as literals (str) and slots (1-tuples), with
    adjacent literals merged."""
    out = list(HEAD) + [f"{level} {comp}: "]
    for lit, field, _spec, _conv in string.Formatter().parse(msg):
        out.append(lit)
        if field is not None:
            out += FIELDS[field]
    out.append("\n")
    merged = []
    for t in out:
        if isinstance(t, str) and merged and isinstance(merged[-1], str):
            merged[-1] += t
        elif t != "":
            merged.append(t)
    return merged


_LINES = [_tokens(lv, comp, msg) for _w, lv, comp, msg in TEMPLATES]
_LITERALS = sorted({t for line in _LINES for t in line if isinstance(t, str)})
_LIT_LEN = [len(t.encode()) for t in _LITERALS]
_LIT_AT = dict(zip(_LITERALS, np.cumsum([0] + _LIT_LEN[:-1]).tolist()))
_LIT_POOL = np.frombuffer("".join(_LITERALS).encode(), np.uint8)
_SLOT_NAMES = list(SLOTS)
_WEIGHTS = np.asarray([t[0] for t in TEMPLATES])
_WEIGHTS = _WEIGHTS / _WEIGHTS.sum()
# an upper bound on a line's length (every slot at full width)
MAX_LINE = max(sum(len(t.encode()) if isinstance(t, str) else SLOTS[t[0]][0]
                   for t in line) for line in _LINES)
MEAN_LINE_GUESS = 140  # bytes; sizes a block's first draw and its id pool


def _digits(values: np.ndarray, width: int, pad: int):
    """Right-aligned decimal digits of ``values`` (uint64) in rows of
    ``width`` bytes, and each row's length (at least ``pad``)."""
    out = np.empty((values.size, width), np.uint8)
    v = values.copy()
    for k in range(width - 1, -1, -1):
        out[:, k] = (v % np.uint64(10)).astype(np.uint8) + ord("0")
        v //= np.uint64(10)
    n = np.ones(values.size, np.int64)
    p = np.uint64(10)
    for _ in range(width - 1):
        n += values >= p
        p *= np.uint64(10)
    return out, np.maximum(n, pad)


def _slot_values(rng, n: int, ids: np.ndarray) -> dict:
    """Every slot's value for ``n`` lines (uint64)."""
    u = np.uint64
    blk = ids[rng.integers(0, ids.size, n)]
    return {
        "day": (81109 + rng.integers(0, 3, n)).astype(u),
        "hms": (rng.integers(0, 24, n) * 10000 + rng.integers(0, 60, n) * 100
                + rng.integers(0, 60, n)).astype(u),
        "thread": rng.integers(1, 4000, n).astype(u),
        "blk_sign": (blk >> u(63)),
        "blk": blk & u((1 << 63) - 1),
        "ip_net": rng.integers(0, 2, n).astype(u),
        "ip_a": rng.integers(1, 255, n).astype(u),
        "ip_b": rng.integers(1, 255, n).astype(u),
        "ip2_net": rng.integers(0, 2, n).astype(u),
        "ip2_a": rng.integers(1, 255, n).astype(u),
        "ip2_b": rng.integers(1, 255, n).astype(u),
        "port": rng.integers(30000, 60000, n).astype(u),
        "size": rng.integers(1, 67108865, n).astype(u),
        "n": rng.integers(0, 3, n).astype(u),
        "sub": rng.integers(0, 64, n).astype(u),
        "job": rng.integers(200811090000, 200811110000, n).astype(u),
        "job_n": rng.integers(1, 9999, n).astype(u),
        "task": rng.integers(0, 2000, n).astype(u),
    }


def _lines(rng, n: int, ids: np.ndarray) -> np.ndarray:
    """``n`` rendered lines, concatenated."""
    which = rng.choice(len(TEMPLATES), n, p=_WEIGHTS)
    values = _slot_values(rng, n, ids)
    pools, lens, base = [_LIT_POOL], {}, _LIT_POOL.size
    slot_start = {}
    for name in _SLOT_NAMES:
        width, pad = SLOTS[name]
        if name == "blk_sign":
            mat = np.full((n, 1), ord("-"), np.uint8)
            ln = values[name].astype(np.int64)
        else:
            mat, ln = _digits(values[name], width, pad)
        pools.append(mat.reshape(-1))
        lens[name] = ln
        # a line's slot starts where its last ``ln`` digits do
        slot_start[name] = base + np.arange(n, dtype=np.int64) * width \
            + (width - ln)
        base += mat.size
    pool = np.concatenate(pools)
    n_seg = np.asarray([len(line) for line in _LINES], np.int64)[which]
    first = np.concatenate([[0], np.cumsum(n_seg)[:-1]])
    seg_start = np.empty(int(n_seg.sum()), np.int64)
    seg_len = np.empty_like(seg_start)
    for t, line in enumerate(_LINES):
        rows = np.flatnonzero(which == t)
        if rows.size == 0:
            continue
        for j, tok in enumerate(line):
            at = first[rows] + j
            if isinstance(tok, str):
                seg_start[at] = _LIT_AT[tok]
                seg_len[at] = len(tok.encode())
            else:
                seg_start[at] = slot_start[tok[0]][rows]
                seg_len[at] = lens[tok[0]][rows]
    end = np.cumsum(seg_len)
    src = np.repeat(seg_start - (end - seg_len), seg_len)
    src += np.arange(int(end[-1]), dtype=np.int64)
    return pool[src]


def _block(seed: int, b: int) -> np.ndarray:
    """Block ``b``: whole lines, at most BLOCK_BYTES long and more than
    BLOCK_BYTES - MAX_LINE."""
    rng = np.random.default_rng([seed, 0x424C, b])
    n_lines = BLOCK_BYTES // MEAN_LINE_GUESS
    n_ids = max(1, int(round(n_lines / LINES_PER_BLOCK_ID)))
    ids = rng.integers(0, 1 << 64, n_ids, dtype=np.uint64)
    parts, total = [], 0
    while total < BLOCK_BYTES:
        more = max(1024, (BLOCK_BYTES - total) // MEAN_LINE_GUESS + 256)
        part = _lines(rng, more, ids)
        parts.append(part)
        total += part.size
    text = np.concatenate(parts)
    # whole lines only: cut after the last newline inside the block
    ends = np.flatnonzero(text[:BLOCK_BYTES] == ord("\n"))
    return text[: int(ends[-1]) + 1]


def hdfs_log_text(n: int, seed: int, *, threads: int = MAX_THREADS) -> np.ndarray:
    """``n`` bytes of HDFS-style log lines from ``seed``, as a uint8 array
    whose last byte is a newline (the last line is cut short to fit)."""
    n_blocks = -(-n // (BLOCK_BYTES - MAX_LINE))
    out = np.empty(n, np.uint8)
    with ThreadPoolExecutor(max_workers=max(1, threads)) as pool_ex:
        blocks = pool_ex.map(lambda b: _block(seed, b), range(n_blocks))
        at = 0
        for blk in blocks:
            take = min(len(blk), n - at)
            out[at : at + take] = blk[:take]
            at += take
            if at == n:
                break
    if at != n:
        raise RuntimeError(f"generated {at} of {n} bytes")
    out[-1] = ord("\n")
    return out
