"""The plain reference for reconstruction: Reed-Solomon decoding by
Gauss-Jordan elimination over GF(2^8), in numpy.

It imports nothing of the program: the field arithmetic and the coding
matrix are `benchmarks.reference`'s (shifts and xors, the published
jerasure `reed_sol_van` construction). A shard j of a stripe is row j of
the generator matrix G = [I_k ; C] applied to the k data rows, so any k
surviving shards give k linear equations in the k unknown data rows:

    G[survivors] . data = shards[survivors]

The elimination is done on the augmented system, data bytes and all:
every row operation on the (k, k) matrix is applied to the (k, n) right
hand side with `gf_mul`, and what is left when the matrix is the
identity is the data. No inverse is formed and no table is used.
"""
from __future__ import annotations

import numpy as np

from benchmarks.reference import _inv, gf_mul, reed_sol_van_matrix


def reconstruct(shards: dict[int, np.ndarray], k: int, m: int) -> np.ndarray:
    """(k, n) uint8 data rows from any k or more of the k+m shard rows
    (`shards`: shard index -> (n,) uint8). The first k by index are
    used; fewer than k cannot be solved and is an error."""
    use = sorted(shards)[:k]
    if len(use) < k or not all(0 <= j < k + m for j in use):
        raise ValueError(f"reconstruct: need {k} of the {k + m} shards, "
                         f"got {sorted(shards)}")
    gen = np.concatenate([np.eye(k, dtype=np.uint8),
                          reed_sol_van_matrix(k, m)])
    a = gen[use].copy()                                    # (k, k)
    b = np.stack([np.asarray(shards[j], dtype=np.uint8) for j in use])
    for col in range(k):
        pivot = next((r for r in range(col, k) if a[r, col]), None)
        if pivot is None:       # any k rows of an MDS generator are regular
            raise ValueError(f"reconstruct: shards {use} are dependent")
        if pivot != col:
            a[[col, pivot]] = a[[pivot, col]]
            b[[col, pivot]] = b[[pivot, col]]
        scale = _inv(int(a[col, col]))
        if scale != 1:
            a[col] = gf_mul(scale, a[col])
            b[col] = gf_mul(scale, b[col])
        for row in range(k):
            c = int(a[row, col])
            if row != col and c:
                a[row] ^= gf_mul(c, a[col])
                b[row] ^= gf_mul(c, b[col])
    return b
