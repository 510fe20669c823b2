"""The benchmark's generators and its plain reference, at tiny sizes."""

import re
import string
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import reference  # noqa: E402
from bench.traffic import hdfs_logs  # noqa: E402
from bench.traffic.stream_scan import CycledSource, expected  # noqa: E402

BIG_SEED = 2**31 + 12345


def find_naive(buf: bytes, pat: bytes) -> np.ndarray:
    out, i = [], buf.find(pat)
    while i >= 0:
        out.append(i)
        i = buf.find(pat, i + 1)
    return np.asarray(out, np.int64)


def test_hdfs_text_repeats_from_a_seed():
    a = hdfs_logs.hdfs_log_text(1 << 20, BIG_SEED)
    b = hdfs_logs.hdfs_log_text(1 << 20, BIG_SEED, threads=1)
    c = hdfs_logs.hdfs_log_text(1 << 20, BIG_SEED + 1)
    assert a.dtype == np.uint8 and a.size == 1 << 20
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a[-1] == ord("\n")
    lines = a.tobytes().split(b"\n")[:-2]
    assert all(line[:6].isdigit() and b" INFO " in line or b" WARN " in line
               or b" ERROR " in line for line in lines)


def test_hdfs_text_holds_dense_and_rare_tokens():
    text = hdfs_logs.hdfs_log_text(1 << 22, 7).tobytes()
    assert text.count(b"blk_") > 20000
    for rare in (b"timeout!", b"segfault", b"too many open files (24)"):
        assert 0 < text.count(rare) < 200


@pytest.fixture(scope="module")
def hdfs_lines():
    """One assembly block of text (16 MiB), as lines."""
    text = hdfs_logs.hdfs_log_text(hdfs_logs.BLOCK_BYTES - hdfs_logs.MAX_LINE,
                                   BIG_SEED)
    return text.tobytes().split(b"\n")[:-2]


def test_hdfs_block_ids_keep_hdfs_v1_cardinality(hdfs_lines):
    ids = [m.group() for line in hdfs_lines
           for m in re.finditer(rb"blk_-?\d+", line)]
    distinct = set(ids)
    # HDFS_v1: 19.43 lines per block id; each id recurs over its life
    assert 15 < len(hdfs_lines) / len(distinct) < 25
    assert len(ids) / len(distinct) > 10
    assert any(i.startswith(b"blk_-") for i in distinct)
    assert any(not i.startswith(b"blk_-") for i in distinct)


def test_hdfs_lines_are_rendered_one_by_one(hdfs_lines):
    assert len(set(hdfs_lines)) > 0.999 * len(hdfs_lines)
    assert max(map(len, hdfs_lines)) < hdfs_logs.MAX_LINE
    mean = sum(map(len, hdfs_lines)) / len(hdfs_lines) + 1
    assert 110 < mean < 160  # HDFS_v1: 1.47 GB over 11.2M lines, 131 B


def _line_pattern(level: str, comp: str, msg: str) -> re.Pattern:
    fields = {
        "blk": r"blk_-?\d{1,19}", "ip": r"10\.25[01]\.\d{1,3}\.\d{1,3}",
        "ip2": r"10\.25[01]\.\d{1,3}\.\d{1,3}", "port": r"\d{5}",
        "size": r"\d{1,8}", "n": r"[0-2]", "sub": r"\d{1,2}",
        "job": r"200811\d{6}_\d{4}", "task": r"\d{6}",
    }
    body = "".join(re.escape(lit) + (fields[f] if f else "")
                   for lit, f, _s, _c in string.Formatter().parse(msg))
    head = r"0811(09|10|11) ([01]\d|2[0-3])[0-5]\d[0-5]\d \d{1,4} "
    return re.compile((head + re.escape(f"{level} {comp}: ") + body)
                      .encode())


@pytest.mark.parametrize("t", range(len(hdfs_logs.TEMPLATES)))
def test_hdfs_template_renders_its_fields(t, hdfs_lines):
    _w, level, comp, msg = hdfs_logs.TEMPLATES[t]
    pat = _line_pattern(level, comp, msg)
    lead = f"{level} {comp}: ".encode() + msg.split("{")[0].encode()
    mine = [line for line in hdfs_lines if pat.fullmatch(line)]
    assert mine, msg
    if msg.count("{blk}") == 2:  # one block id, named twice
        assert all(len(set(re.findall(rb"blk_-?\d+", line))) == 1
                   for line in mine)
    share = len(mine) / len(hdfs_lines)
    weight = hdfs_logs.TEMPLATES[t][0] / sum(x[0] for x in hdfs_logs.TEMPLATES)
    assert share == pytest.approx(weight, rel=0.2, abs=2e-4), lead


def test_every_line_is_one_of_the_templates(hdfs_lines):
    pats = [_line_pattern(lv, c, m) for _w, lv, c, m in hdfs_logs.TEMPLATES]
    sample = hdfs_lines[:: max(1, len(hdfs_lines) // 5000)]
    assert all(any(p.fullmatch(line) for p in pats) for line in sample)


@pytest.mark.parametrize("value,pad,want", [
    (0, 0, b"0"), (7, 0, b"7"), (10, 0, b"10"), (81109, 6, b"081109"),
    (2**63 - 1, 0, b"9223372036854775807"), (42, 4, b"0042"),
])
def test_digits_render_decimal(value, pad, want):
    rows, lens = hdfs_logs._digits(np.asarray([value], np.uint64), 19, pad)
    assert rows[0, 19 - int(lens[0]):].tobytes() == want


def test_cycled_source_cycles_and_stops():
    buf = np.arange(100, dtype=np.uint8)
    src = CycledSource(buf, 30, limit=250)
    got = np.concatenate(list(src))
    assert src.bytes == got.size and 250 <= got.size < 250 + 30
    assert np.array_equal(got, np.resize(buf, got.size))
    again = np.concatenate(list(CycledSource(buf, 30, limit=250)))
    assert np.array_equal(got, again)


@pytest.mark.parametrize("total", [0, 99, 100, 257])
def test_expected_positions_over_cycles(total):
    buf = b"ab\nab" * 20 + b"\n"
    arr = np.frombuffer(buf, np.uint8)
    ref = reference.find_all(arr, [b"ab"])[0]
    stream = (buf * 4)[:total]
    assert np.array_equal(expected(ref, 2, len(buf), total),
                          find_naive(stream, b"ab"))


@pytest.mark.parametrize("text_kind", ["hdfs", "tiny_alphabet", "short"])
def test_reference_equals_a_find_loop(text_kind):
    rng = np.random.default_rng(5)
    if text_kind == "short":
        text = hdfs_logs.hdfs_log_text(3000, 1)
    else:
        text = hdfs_logs.hdfs_log_text(1 << 18, 2)
        if text_kind == "tiny_alphabet":  # dense overlapping occurrences
            text = rng.integers(97, 100, text.size).astype(np.uint8)
    buf = text.tobytes()
    pats = [b"a", b"ab", b"aba", b"abababab", b"ababababa", b"blk_",
            b"timeout!", b"too many open files (24)", buf[:9], buf[-5:]]
    for _ in range(40):
        m = int(rng.integers(1, 33))
        s = int(rng.integers(0, len(buf) - m))
        pats.append(buf[s : s + m])
    got = reference.find_all(text, pats)
    for p, g in zip(pats, got):
        assert np.array_equal(g, find_naive(buf, p)), p
