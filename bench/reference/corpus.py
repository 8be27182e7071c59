"""The synthetic corpus, copied from the program's data layer.

A seeded, seekable token stream: Zipf(1.2) token ids folded into
[2, vocab), with BOS (id 1) at document boundaries, drawn per block of 1024
positions from ``numpy.random.default_rng((seed, block))``.  The benchmark
keeps its own copy so that the reference and the fan-out's host tasks read
the same rows without asking the program, and so that the check can hold the
program's loader to them.
"""
from __future__ import annotations

import numpy as np

BLOCK = 1024
ZIPF_A = 1.2
DOC_LEN_MEAN = 512


def tokens_at(seed: int, vocab: int, cursor: int, n: int) -> np.ndarray:
    out = np.empty(n, dtype=np.int32)
    got, block, off = 0, cursor // BLOCK, cursor % BLOCK
    while got < n:
        rng = np.random.default_rng((seed, block))
        toks = rng.zipf(ZIPF_A, size=BLOCK).astype(np.int64)
        toks = (toks - 1) % max(2, vocab - 2) + 2
        toks[rng.random(BLOCK) < 1.0 / DOC_LEN_MEAN] = 1
        take = min(BLOCK - off, n - got)
        out[got:got + take] = toks[off:off + take]
        got, block, off = got + take, block + 1, 0
    return out


def rows(seed: int, vocab: int, cursor: int, n_rows: int, seq: int):
    """(tokens, targets) of ``n_rows`` consecutive spans of seq + 1 tokens."""
    flat = tokens_at(seed, vocab, cursor, n_rows * (seq + 1))
    flat = flat.reshape(n_rows, seq + 1)
    return flat[:, :-1], flat[:, 1:]
