"""Share of the HBM roofline the Chebyshev recurrence reaches, in %.

The least bytes one recurrence step must move (``roofline.step_bytes``),
times the steps the window's filters ran, at the chip's peak HBM
bandwidth (``peaks.json``), over the device-busy time inside the
harness's spans around ``step_filter``, taken from the trace. Whatever
engine or kernel runs the step, its yardstick stays this one. A device
kind missing from the peaks table is an error, not a default."""

from roofline import step_bytes


def read(rec):
    steps = rec.counts.get("filter_steps")
    if rec.trace is None or not steps:
        return None
    busy = rec.trace.span_busy_s("filter")
    if busy <= 0:
        return None
    cfg = rec.cell.config
    least = steps * step_bytes(cfg["nnz"], cfg["D"],
                               cfg["solver"]["n_search"], cfg["dtype"])
    return 100.0 * least / rec.peaks[rec.kind]["hbm_bytes_per_s"] / busy
