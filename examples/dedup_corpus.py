"""The paper's technique as data infrastructure: LSH near-duplicate
detection over a token corpus (fingerprint → Min-Max LSH → postprocess,
exactly the FAST pipeline shape).

Run:  PYTHONPATH=src python examples/dedup_corpus.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.data.dedup import DedupConfig, find_duplicates


def main():
    enable_compile_cache()
    rng = np.random.default_rng(0)
    n, s = 64, 256
    docs = rng.integers(1, 50_000, (n, s)).astype(np.int32)
    # inject: 8 exact duplicates + 8 near-duplicates (2% token noise)
    for j in range(8):
        docs[n - 16 + j] = docs[j]
    for j in range(8):
        d = docs[8 + j].copy()
        flips = rng.integers(0, s, size=s // 50)
        d[flips] = rng.integers(1, 50_000, size=flips.size)
        docs[n - 8 + j] = d

    keep, stats = find_duplicates(docs, DedupConfig())
    print(f"corpus: {n} docs × {s} tokens; injected 16 (near-)duplicates")
    print(f"candidate pairs from LSH: {stats['candidate_pairs']}, "
          f"verified: {stats['verified_dups']}, dropped: {stats['dropped']}")
    dropped = np.where(~keep)[0]
    print(f"dropped doc ids: {dropped.tolist()}")
    assert stats["dropped"] >= 14, stats
    assert keep[:48].sum() >= 46  # originals survive
    print("OK")


if __name__ == "__main__":
    main()
