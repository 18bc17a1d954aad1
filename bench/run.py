"""Benchmark of the filter-diagonalization eigensolver on TPU: one run of
one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cells, their metrics and the bounds are in ``BENCHMARK.json`` at the
root of the checkout. A run sets up the cell (matrix build, Lanczos,
warm-up, compilation or persistent-cache reads: ``setup_s``), measures for
``--seconds`` on the host clock, frees the device, checks what the window
produced against a plain host reference, and prints one JSON line last on
stdout: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics, read from
a profiler trace of the window), ``device`` and, traced, ``breakdown``;
then ``checks``, each number compared beside its limit, which are also the
last lines on stderr. Off a TPU, or with fewer chips than the cell needs,
it exits 3 and prints no line.

Adding to it takes new files and new entries in ``BENCHMARK.json``, never
an edit of a file that is here:

* a configuration: ``bench/configs/<config>.json`` (family, params, dtype,
  D, nnz, mesh, FD's settings, the source and every cut), and for a new
  family ``bench/references/<Family>.py`` with ``build_csr(**params)``, a
  plain scipy build of the matrix written from its definition;
* a traffic mix: ``bench/traffic/<traffic>.json``, naming the loop kind
  that drives it and its data (target, degree cap, warm degrees ...); a
  new loop kind is ``bench/loops/<loop>.py`` with a class ``Loop`` (warm,
  programs, window, fetch, check);
* a cell: ``bench/workloads/<cell>.json`` with the limits of its check;
* a metric: ``bench/metrics/<metric>.py`` with ``read(record)``, which
  returns a number or None where it finds nothing to read.

The persistent compilation cache is where JAX_COMPILATION_CACHE_DIR says,
else ``bench/_cache/jax``; traces go to ``bench/_cache/trace`` and are
deleted once read.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    cell = harness.load_cell(args.workload)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        line = harness.run_cell(cell, args.seed, args.seconds,
                                bool(args.trace), T_START, log=log)
    except harness.NoDevice as e:
        log(f"bench: {e}; it runs on the chip only")
        return 3
    for name, c in line["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
