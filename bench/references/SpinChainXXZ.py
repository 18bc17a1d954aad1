"""Plain host reference of the SpinChainXXZ family (ScaMaC's generator).

XXZ spin-1/2 chain with open boundaries in the sector of ``n_up`` up
spins on ``n_sites`` sites:

    H = sum_i (Jxy/2) (S+_i S-_{i+1} + h.c.) + Jz Sz_i Sz_{i+1}

The basis is every ``n_sites``-bit mask with ``n_up`` bits set, in
increasing order. A bond whose two bits differ swaps them with amplitude
Jxy/2; the diagonal is Jz * sum_i (n_i - 1/2)(n_{i+1} - 1/2). Written
from that definition alone, in float64 with scipy.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def build_csr(n_sites: int, n_up: int, Jxy: float = 1.0, Jz: float = 1.0):
    masks = np.arange(1 << n_sites, dtype=np.int64)
    count = np.zeros_like(masks)
    for i in range(n_sites):
        count += (masks >> i) & 1
    basis = masks[count == n_up]
    occ = ((basis[:, None] >> np.arange(n_sites)) & 1).astype(np.float64)
    diag = Jz * ((occ[:, :-1] - 0.5) * (occ[:, 1:] - 0.5)).sum(axis=1)
    rows = [np.arange(basis.size)]
    cols = [np.arange(basis.size)]
    vals = [diag]
    for i in range(n_sites - 1):
        hop = np.nonzero(((basis >> i) ^ (basis >> (i + 1))) & 1)[0]
        rows.append(hop)
        cols.append(np.searchsorted(basis, basis[hop] ^ (3 << i)))
        vals.append(np.full(hop.size, 0.5 * Jxy))
    D = basis.size
    return sp.csr_matrix((np.concatenate(vals),
                          (np.concatenate(rows), np.concatenate(cols))),
                         shape=(D, D))
