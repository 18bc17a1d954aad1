"""Redistribution of vectors between the stack and panel layouts (paper §3.4).

The redistribution is the explicit price paid for running the Chebyshev
filter in a panel/pillar layout (vertical layer active) while
orthogonalization runs in the stack layout (horizontal layer only,
``layouts.py``). Each process row exchanges slices with itself only —
for "matching" layouts the collective never crosses the ``row`` axes::

      stack (4x2 mesh, N_col=2)            panel (4x2)
      +----------+                      +------+------+
      | p0       |  <- row 0 ->         | p0   | p1   |    all_to_all
      | p1       |                      |      |      |    within the
      +----------+                      +------+------+    pair {p0,p1}
      | p2       |  <- row 1 ->         | p2   | p3   |    (and {p2,p3},
      | p3       |                      |      |      |     ...): tiles
      +----------+                      +------+------+    of D/P x N_s/2
      |  ...     |                      |     ...     |
      +----------+                      +-------------+

Per device this moves exactly (N_s·D/P)(1 − 1/N_col) entries each way
(Eqs. 17–18, :func:`redistribution_volume`); the planner
(``planner.py``) charges two such exchanges per filter pass when ranking
panel/pillar candidates against the redistribution-free stack. Amortized
over a degree-n filter the cost is r/n Chebyshev iterations (Eqs. 19–21,
``perf_model.redistribution_factor``).

Two implementations (Alg. 1 steps 7 and 9):

  * ``explicit`` — the paper-faithful collective: one `all_to_all` along
    the vertical (``col``) mesh axes, tiled over the N_s axis on the way
    out and the D axis on the way back. For matching layouts communication
    stays strictly within a panel row (paper Fig. 6) — in mesh terms the
    collective never crosses the ``row`` axes. Volume per device is
    exactly (N_s·D/P)(1 − 1/N_col) entries (Eqs. 17–18).

  * ``gspmd`` — `lax.with_sharding_constraint` to the target sharding;
    XLA chooses the collective schedule. Used as a §Perf comparison point.

Shuffling for contiguous storage (paper Fig. 6 right) is XLA's problem on
TPU — the tiled all_to_all already produces the canonical layout.
"""
from __future__ import annotations

import jax
from jax import lax
from jax import shard_map
from jax.sharding import Mesh

from .layouts import Layout

__all__ = ["make_redistribute", "redistribution_volume"]


def redistribution_volume(D: int, N_s: int, P_total: int, N_col: int, S_d: int) -> dict:
    """Exact communication volumes of one redistribution (Eqs. 17–18)."""
    per_row = N_s * D * (N_col - 1) / P_total * S_d
    total = N_s * D * (1 - 1.0 / N_col) * S_d
    return {"bytes_per_process_row": per_row, "bytes_total": total}


def make_redistribute(mesh: Mesh, stack_layout: Layout, panel_layout: Layout,
                      impl: str = "explicit"):
    """Return (to_panel(V), to_stack(V)) closures.

    ``stack_layout`` must shard D over all mesh axes with the panel's row
    axes leading, so that the stack slice index b = i_row * N_col + j_col
    gives the paper's "matching layouts" (communication only within panel
    rows).
    """
    col_axes = panel_layout.bundle_axes
    if not col_axes:  # N_col = 1: layouts coincide
        return (lambda V: V), (lambda V: V)
    if impl == "gspmd":
        s_stack = stack_layout.vec_sharding(mesh)
        s_panel = panel_layout.vec_sharding(mesh)

        def to_panel(V):
            with jax.named_scope("fd.to_panel"):
                return lax.with_sharding_constraint(V, s_panel)

        def to_stack(V):
            with jax.named_scope("fd.to_stack"):
                return lax.with_sharding_constraint(V, s_stack)

        return to_panel, to_stack

    if impl != "explicit":
        raise ValueError(f"unknown redistribution impl {impl!r}")

    stack_spec = stack_layout.vec_pspec()
    panel_spec = panel_layout.vec_pspec()

    def _to_panel_local(Vb):
        # Vb: stack-local [D/P, N_s] -> panel-local [D/N_row, N_s/N_col]
        with jax.named_scope("fd.to_panel"):
            return lax.all_to_all(Vb, col_axes, split_axis=1, concat_axis=0,
                                  tiled=True)

    def _to_stack_local(Vb):
        # Vb: panel-local [D/N_row, N_s/N_col] -> stack-local [D/P, N_s]
        with jax.named_scope("fd.to_stack"):
            return lax.all_to_all(Vb, col_axes, split_axis=0, concat_axis=1,
                                  tiled=True)

    to_panel = shard_map(_to_panel_local, mesh=mesh, in_specs=(stack_spec,),
                         out_specs=panel_spec, check_vma=False)
    to_stack = shard_map(_to_stack_local, mesh=mesh, in_specs=(panel_spec,),
                         out_specs=stack_spec, check_vma=False)
    return to_panel, to_stack
