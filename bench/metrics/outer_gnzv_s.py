"""Outer-loop throughput: stored nonzeros times the vector SpMVs FD
counted (filter and Rayleigh-Ritz), over the whole time of the window's
completed outer iterations, in 1e9 per second."""


def read(rec):
    if not rec.counts.get("iterations"):
        return None
    return rec.cell.config["nnz"] * rec.counts["spmvs"] / rec.window_s / 1e9
