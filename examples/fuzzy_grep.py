"""fuzzy_grep — typo-tolerant multi-pattern search with repro.approx.

    PYTHONPATH=src python examples/fuzzy_grep.py [--k 1] [--size 200000]

Plants corrupted copies of a query into a synthetic corpus and contrasts the
exact packed matcher (misses them) with the k-mismatch engine (finds them):
the fuzzy-grep / DNA-read-filter / typo-blocklist workload in ~60 lines.
One engine dispatch answers all queries x all budgets' worth of texts; see
DESIGN.md §8 for the packed counting filter + relaxed fingerprint gate.
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.approx import kmismatch_naive
from repro.compile_cache import configure_compile_cache
from repro.core import engine
from repro.data import corpus


def main():
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--k", type=int, default=1)
    ap.add_argument("--size", type=int, default=200_000)
    args = ap.parse_args()

    rng = np.random.RandomState(42)
    text = np.array(corpus.make_corpus("english", args.size, seed=1))
    query = text[5_000:5_012].copy()  # m = 12 window from the corpus itself

    # plant 3 corrupted copies: 1 typo, args.k typos, args.k + 1 typos
    sites = {}
    for i, typos in enumerate((1, args.k, args.k + 1)):
        site = 20_000 + 30_000 * i
        w = query.copy()
        for j in rng.choice(len(w), size=typos, replace=False):
            w[j] ^= rng.randint(1, 256)
        text[site : site + len(w)] = w
        sites[site] = typos

    idx = engine.build_index(text)
    for k in (0, args.k):
        plans = engine.compile_patterns([query], k=k)
        mask = np.asarray(engine.match_many_jit(idx, plans, k=k))[0, 0]
        hits = np.nonzero(mask)[0]
        naive = np.nonzero(kmismatch_naive(text, query, k))[0]
        assert np.array_equal(hits, naive), "engine/naive divergence"
        planted = [s for s in sites if s in set(hits.tolist())]
        print(
            f"k={k}: {len(hits)} hit(s) at {hits.tolist()[:8]} "
            f"(planted sites found: {planted})"
        )
        for s, typos in sites.items():
            status = "FOUND" if s in set(hits.tolist()) else "missed"
            print(f"    site {s} ({typos} typo(s)): {status}")
    print("ok")


if __name__ == "__main__":
    main()
