"""One run of one cell: set-up, the measured window, the check, the line.

Everything that belongs to one configuration, traffic mix, metric or cell
is a file of its own, found by the name ``BENCHMARK.json`` gives it:

    bench/configs/<config>.json      the matrix, its precision, FD's settings
    bench/references/<family>.py     plain host build of the family's matrix
    bench/traffic/<traffic>.json     a mix: which loop drives it, and its data
    bench/loops/<loop>.py            a loop kind: warm-up, window and check
    bench/workloads/<cell>.json      the limits of the cell's check
    bench/metrics/<metric>.py        read(record) -> number, or None

``run_cell`` loads them, builds the solver, warms up, measures for the
given seconds with the host clock (and, when traced, the profiler), frees
the device, runs the reference on the host, and returns the result line.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import os
import shutil
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list     # BENCHMARK.json metric entries this cell reports
    per_layer: list


def load_cell(name: str) -> Cell:
    spec = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    (cfg,) = [c for c in spec["configs"] if c["name"] == w["config"]]

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return Cell(
        name=name, chips=w["chips"],
        config=read_json(os.path.join(ROOT, cfg["file"])),
        traffic=read_json(os.path.join(BENCH, "traffic",
                                       w["traffic"] + ".json")),
        limits=read_json(os.path.join(BENCH, "workloads",
                                      name + ".json"))["limits"],
        end_to_end=mine(spec["end_to_end"]),
        per_layer=mine(spec["per_layer"]))


def check_device(chips: int):
    """The devices to run on; raises ``NoDevice`` off a TPU, never falls
    back to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (backend {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else at one fixed path inside the checkout."""
    import jax

    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(BENCH, "_cache", "jax"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Spans:
    """Host-clock spans around the harness's calls into the solver, also
    written into the profiler's trace as ``bench.<name>``. Each span ends
    after the device result it waits for, so it holds the device time."""

    def __init__(self):
        self.items = []      # (name, start, end), time.perf_counter()

    def __call__(self, name: str):
        return _Span(self, name)

    def total(self, name: str, lo: float = float("-inf"),
              hi: float = float("inf")) -> tuple[float, int]:
        """(seconds, count) of the spans ``name`` inside [lo, hi]."""
        d = [e - s for n, s, e in self.items if n == name and lo <= s
             and e <= hi]
        return sum(d), len(d)


class _Span:
    def __init__(self, spans: Spans, name: str):
        import jax

        self.spans, self.name = spans, name
        self.annotation = jax.profiler.TraceAnnotation("bench." + name)

    def __enter__(self):
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.spans.items.append((self.name, self.t0, time.perf_counter()))
        return self.annotation.__exit__(*exc)


@dataclasses.dataclass
class Record:
    """What the metric readers read: one run's counts, spans and trace."""
    cell: Cell
    kind: str                # device_kind, the key into peaks
    peaks: dict              # bench/peaks.json
    setup_s: float
    window: tuple            # (start, end), host clock
    counts: dict             # the loop's own counts over the window
    spans: Spans
    program_bytes: dict      # program -> HBM it needs (memory_analysis)
    trace: object = None     # trace.Trace of the window, when traced

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def make_solver(cell: Cell, devs):
    """The system under test: FilterDiag on the configuration's matrix."""
    from repro.core import FDConfig, FilterDiag, make_solver_mesh
    from repro.matrices import get_family

    cfg = cell.config
    n_row, n_col = cfg["mesh"]
    mesh = make_solver_mesh(n_row, n_col, devices=devs[: n_row * n_col])
    fd_cfg = FDConfig(dtype=cfg["dtype"], **cfg["solver"],
                      **cell.traffic.get("solver", {}))
    fd = FilterDiag(get_family(cfg["family"], **cfg["params"]), mesh, fd_cfg)
    if fd.D != cfg["D"] or fd.rowmap is not None:
        raise RuntimeError(f"solver built D={fd.D} (rowmap {fd.rowmap}), "
                           f"configuration says D={cfg['D']}")
    return fd


def lower(fn, *args):
    """``fn`` (a jitted function, or a partial of one) lowered for args."""
    if isinstance(fn, functools.partial):
        return fn.func.lower(*fn.args, *args, **fn.keywords)
    return fn.lower(*args)


def program_bytes(programs: dict) -> dict:
    """HBM each program needs: arguments + outputs + temporaries - aliased,
    from the compiler's ``memory_analysis()``."""
    out = {}
    for name, lowered in programs.items():
        m = lowered.compile().memory_analysis()
        out[name] = (m.argument_size_in_bytes + m.output_size_in_bytes
                     + m.temp_size_in_bytes - m.alias_size_in_bytes)
    return out


def device_line(devs, cell: Cell) -> dict:
    d = devs[0]
    peak = max((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for x in devs[: cell.chips])
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_start: float, device_check=check_device, solver_hook=None,
             log=print) -> dict:
    """One run of ``cell``; returns the result line as a dict.

    ``device_check`` and ``solver_hook`` let the tests drive the rest of a
    run on the CPU, and plant a fault in the solver it builds."""
    import jax

    from devtrace import Trace

    devs = device_check(cell.chips)
    jax.config.update("jax_enable_x64", True)
    log(f"[setup] compile cache {enable_compile_cache()}")
    spans = Spans()
    t_build = time.perf_counter()
    fd = make_solver(cell, devs)
    if solver_hook is not None:
        solver_hook(fd)
    loop = load_module(os.path.join(
        BENCH, "loops", cell.traffic["loop"] + ".py")).Loop(
        fd, cell.traffic, seed, spans)
    t_warm = time.perf_counter()
    loop.warm()
    t_hbm = time.perf_counter()
    need = program_bytes(loop.programs())
    setup_s = time.perf_counter() - t_start
    log(f"[setup] {setup_s:.3f} s: start {t_build - t_start:.3f}, build "
        f"{t_warm - t_build:.3f}, warm-up {t_hbm - t_warm:.3f}, HBM "
        f"readings {t_start + setup_s - t_hbm:.3f}; program HBM {need}")

    trace_dir = os.path.join(BENCH, "_cache", "trace", cell.name)
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with spans("window"):
        t0 = time.perf_counter()
        counts = loop.window(seconds, traced)
        t1 = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    device = device_line(devs, cell)
    log(f"[window] {t1 - t0:.3f} s: {counts}")

    loop.fetch()
    del fd
    gc.collect()
    trace = None
    if traced:
        t_read = time.perf_counter()
        trace = Trace.from_xplane(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"[trace] {sum(map(len, trace.ops.values()))} device ops, "
            f"{len(trace.spans)} spans, read in "
            f"{time.perf_counter() - t_read:.3f} s")
        lo, hi = trace.window()
        if trace.ops_end() < hi - 0.01 * (hi - lo):
            log(f"[trace] device ops end {(hi - trace.ops_end()) * 1e-9:.3f}"
                " s before the window: the profiler dropped events")
        device["busy_s"] = trace.busy_s(lo, hi)
        device["window_s"] = (hi - lo) * 1e-9

    t_ref = time.perf_counter()
    family = load_module(os.path.join(BENCH, "references",
                                      cell.config["family"] + ".py"))
    A = family.build_csr(**cell.config["params"])
    numbers, checked = loop.check(A)
    log(f"[check] reference {time.perf_counter() - t_ref:.3f} s")
    checks = {k: {"value": v, "limit": cell.limits[k]}
              for k, v in numbers.items()}
    correct = (A.shape[0] == cell.config["D"] and A.nnz == cell.config["nnz"]
               and checked > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))

    rec = Record(cell=cell, kind=device["kind"],
                 peaks=read_json(os.path.join(BENCH, "peaks.json")),
                 setup_s=setup_s,
                 window=(t0, t1), counts=counts, spans=spans,
                 program_bytes=need,
                 trace=trace)
    metrics = {}
    for m in cell.per_layer if traced else cell.end_to_end:
        v = load_module(os.path.join(BENCH, "metrics",
                                     m["name"] + ".py")).read(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    line = {"correct": bool(correct), "attempted": counts["attempted"],
            "failed": counts["failed"], "metrics": metrics, "device": device}
    if traced:
        lo, hi = trace.window()
        line["breakdown"] = {"device_ops": trace.top_ops(lo, hi),
                             "idle_gaps": trace.idle_gaps(lo, hi)}
    line["checks"] = checks
    return line
