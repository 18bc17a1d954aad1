"""The least HBM traffic of one Chebyshev recurrence step.

One step T_k = 2 alpha A T_{k-1} + 2 beta T_{k-1} - T_{k-2} on a D x n_vec
block, run as one step, must read each stored entry of A once (its value
and a 4-byte column index), read T_{k-1} and T_{k-2}, and write T_k. The
running sum Y and any padding only add to this. A kernel that kept
several steps' blocks in fast memory could move less; a share built on
this count would then pass 100%, and the count has to change with it.
"""
from __future__ import annotations

import numpy as np

INDEX_BYTES = 4


def step_bytes(nnz: int, D: int, n_vec: int, dtype: str) -> int:
    s = np.dtype(dtype).itemsize
    return nnz * (s + INDEX_BYTES) + 3 * D * n_vec * s
