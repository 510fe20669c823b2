"""Quickstart: EPSM packed string matching on the paper's three corpora.

    PYTHONPATH=src python examples/quickstart.py [--size 1000000]
"""

import argparse
import time

import numpy as np

import jax

from repro.compile_cache import configure_compile_cache
from repro.core import baselines, epsm
from repro.core.multipattern import PatternSet, find_multi
from repro.data import corpus


def main():
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=1_000_000)
    args = ap.parse_args()

    print("=== EPSM quickstart ===")
    text = b"The quick brown fox jumps over the lazy dog. The dog sleeps."
    for pat in (b"The", b"dog", b"quick brown fox ", b"cat"):
        pos = epsm.positions(text, pat)
        print(f"  find({pat!r}) -> positions {list(pos)}")

    ps = PatternSet([b"fox", b"cat", b"dog"])
    print(f"  blocklist hit: {bool(ps.contains_any(text))}")

    print(f"\n=== throughput on {args.size/1e6:.1f}MB corpora ===")
    for name in ("genome", "protein", "english"):
        t = corpus.make_corpus(name, args.size, seed=0)
        row = [name]
        for m in (2, 8, 24):
            p = corpus.extract_patterns(t, m, 1, seed=1)[0]
            fn = jax.jit(lambda tt, pp: epsm.find(tt, pp))
            mask = fn(t, p)
            mask.block_until_ready()  # compile
            t0 = time.perf_counter()
            for _ in range(3):
                fn(t, p).block_until_ready()
            dt = (time.perf_counter() - t0) / 3
            occ = int(mask.sum())
            row.append(f"m={m}: {args.size/dt/1e9:.2f} GB/s ({occ} occ)")
        print(" ", " | ".join(row))

    print("\n=== cross-check vs scalar oracle ===")
    t = corpus.make_corpus("genome", 20_000, seed=2)
    p = corpus.extract_patterns(t, 16, 1, seed=3)[0]
    assert np.array_equal(np.asarray(epsm.find(t, p)), baselines.naive_np(t, p))
    print("  EPSM == oracle  OK")


if __name__ == "__main__":
    main()
