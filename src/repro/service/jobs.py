"""Resumable FilterDiag jobs: FDState <-> checkpoint bridge + job driver.

``FilterDiag.step`` advances an explicit :class:`~repro.core.filter_diag.FDState`
one outer iteration at a time; this module makes that state durable. A
state is split into

  * a **pytree** — the search block ``V`` (the only device array the
    loop carries; every other per-iteration quantity is recomputed from
    it), saved as a leaf by ``checkpoint.save``,
  * a **manifest extra** — the host-side fields (Lanczos interval,
    iteration counter, SpMV/redistribution tallies, history, and the
    finished result, if any) as plain JSON. Floats survive the JSON
    round trip exactly (repr round-trip), so a restored solve continues
    on bit-identical host data.

The RowMap a planned partition solved on is *not* checkpointed: the job
is reconstructed from its config (matrix + plan) and ``plan_rowmap`` is
deterministic, so the rebuilt solver carries the identical map; the
manifest records the map's fingerprint (D/P/R + boundary/perm hashes)
and ``unpack_state`` refuses to resume onto a mismatched one — a solve
checkpointed under one row decomposition must never silently continue
under another.

:class:`FilterDiagJob` implements the job protocol the runtime
supervisor drives (``runtime/supervisor.py`` ``run_job``): template /
init / step / pack / unpack / done. A job killed mid-Chebyshev-sweep
resumes from the last committed iteration boundary and converges to the
same eigenpairs (tests/test_service.py injects exactly that fault).
"""
from __future__ import annotations

import hashlib
from typing import Any

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from ..core.filter_diag import FDResult, FDState, FilterDiag

__all__ = ["rowmap_fingerprint", "pack_state", "unpack_state",
           "state_template", "FilterDiagJob"]


def rowmap_fingerprint(rowmap) -> str | None:
    """Stable fingerprint of a planned row decomposition (None for the
    equal-rows identity partition)."""
    if rowmap is None:
        return None
    h = hashlib.sha256()
    h.update(f"{rowmap.D}/{rowmap.P}/{rowmap.R}/{rowmap.sstep}".encode())
    h.update(np.ascontiguousarray(rowmap.perm, dtype=np.int64).tobytes())
    h.update(np.ascontiguousarray(rowmap.boundaries,
                                  dtype=np.int64).tobytes())
    return h.hexdigest()[:16]


def _result_to_json(r: FDResult | None):
    if r is None:
        return None
    return {
        "eigenvalues": [float(x) for x in np.asarray(r.eigenvalues)],
        "residuals": [float(x) for x in np.asarray(r.residuals)],
        "n_converged": int(r.n_converged), "iterations": int(r.iterations),
        "total_spmvs": int(r.total_spmvs),
        "redistributions": int(r.redistributions),
        "history": r.history,
    }


def _result_from_json(j) -> FDResult | None:
    if j is None:
        return None
    return FDResult(
        eigenvalues=np.asarray(j["eigenvalues"], dtype=np.float64),
        residuals=np.asarray(j["residuals"], dtype=np.float64),
        n_converged=int(j["n_converged"]), iterations=int(j["iterations"]),
        total_spmvs=int(j["total_spmvs"]),
        redistributions=int(j["redistributions"]),
        history=_history_from_json(j["history"]),
    )


def _history_from_json(hist) -> list:
    # JSON turns the search tuple into a list; restore the native shape
    return [dict(h, search=tuple(h["search"])) for h in hist]


def pack_state(state: FDState, fd: FilterDiag) -> tuple[dict, dict]:
    """(pytree, extra) of a state at an iteration boundary."""
    assert state.pending is None, \
        "checkpoint only at iteration boundaries (pending filter unset)"
    extra = {
        "lam": [float(state.lam[0]), float(state.lam[1])],
        "iteration": int(state.iteration),
        "total_spmvs": int(state.total_spmvs),
        "redistributions": int(state.redistributions),
        "history": state.history,
        "done": bool(state.done),
        "result": _result_to_json(state.result),
        "rowmap": rowmap_fingerprint(fd.rowmap),
    }
    return {"V": state.V}, extra


def unpack_state(tree: dict, extra: dict, fd: FilterDiag) -> FDState:
    """Rebuild an FDState from a restored (pytree, extra) pair, verifying
    the solver's row decomposition matches the one checkpointed. Keys the
    state no longer has (older manifests' ``wall_time``/``redist_time``)
    are ignored."""
    saved = extra.get("rowmap")
    here = rowmap_fingerprint(fd.rowmap)
    if saved != here:
        raise ValueError(f"checkpointed rowmap {saved!r} does not match the "
                         f"solver's {here!r} — a solve must resume on the "
                         f"row decomposition it was planned with")
    return FDState(
        V=tree["V"], lam=tuple(extra["lam"]),
        iteration=int(extra["iteration"]),
        total_spmvs=int(extra["total_spmvs"]),
        redistributions=int(extra["redistributions"]),
        history=_history_from_json(extra["history"]),
        done=bool(extra["done"]),
        result=_result_from_json(extra.get("result")),
    )


def state_template(fd: FilterDiag, n_search: int | None = None) -> dict:
    """Zero pytree with the checkpointed structure/shapes — what
    ``checkpoint.restore`` needs to re-materialize a state without
    running the (expensive) Lanczos init."""
    n_s = n_search if n_search is not None else fd.cfg.n_search
    return {"V": jnp.zeros((fd.D_pad, n_s), dtype=fd.dtype)}


class FilterDiagJob:
    """One resumable solve: the job protocol ``Supervisor.run_job`` drives.

    ``init`` runs Lanczos + the random search draw; ``step`` is one outer
    FD iteration; ``pack``/``unpack`` bridge to ``checkpoint/``. The
    V-leaf spec is the stack layout's PartitionSpec so an elastic restore
    re-shards straight onto the (possibly different) mesh.
    """

    def __init__(self, fd: FilterDiag, key=None, verbose: bool = False):
        self.fd = fd
        self.key = key
        self.verbose = verbose
        self.mesh = fd.mesh
        self.specs = {"V": fd.stack_layout.vec_pspec()}

    def template(self) -> dict:
        return state_template(self.fd)

    def init(self) -> FDState:
        state = self.fd.init_state(self.key)
        return state

    def step(self, state: FDState) -> FDState:
        return self.fd.step(state, verbose=self.verbose)

    def done(self, state: FDState) -> bool:
        return state.done

    def step_index(self, state: FDState) -> int:
        return state.iteration

    def pack(self, state: FDState) -> tuple[dict, dict]:
        return pack_state(state, self.fd)

    def unpack(self, tree: dict, extra: dict) -> FDState:
        state = unpack_state(tree, extra, self.fd)
        # restored leaves may arrive replicated — pin the stack sharding
        state.V = jnp.asarray(state.V)
        if self.mesh is not None:
            state.V = jax.device_put(
                state.V, NamedSharding(self.mesh, self.specs["V"]))
        return state

    def result(self, state: FDState) -> Any:
        return state.result
