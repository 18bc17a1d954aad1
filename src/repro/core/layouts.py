"""Distributed vector layouts — the two orthogonal layers of parallelism.

The search vectors form a D x N_s matrix V. A *panel* layout distributes V
over an (N_row x N_col) Cartesian process grid (paper Fig. 3):

  * horizontal layer — the D axis is sliced across ``N_row`` processes
    (SpMV communicates along this axis: each shard gathers the remote
    vector entries its nonzeros reference — the χ metric counts them),
  * vertical layer   — the N_s axis is sliced across ``N_col`` process
    columns into bundles of N_s/N_col vectors (no SpMV communication
    crosses it; orthogonalization communicates along this axis).

The three named layouts on a P = 8 device mesh, showing which slice of
the D x N_s vector block each device p0..p7 owns::

        stack (8x1)          panel (4x2)          pillar (1x8)
      N_col = 1            N_row x N_col        N_row = 1
      +-----------+        +-----+-----+      +--+--+--+--+--+--+--+--+
   D  | p0        |        | p0  | p1  |      |p0|p1|p2|p3|p4|p5|p6|p7|
   |  | p1        |        +-----+-----+      |  |  |  |  |  |  |  |  |
   v  | p2        |        | p2  | p3  |      |  |  |  |  |  |  |  |  |
      |  ...      |        +-----+-----+      |  |  |  |  |  |  |  |  |
      | p7        |        |  ...      |      |  |  |  |  |  |  |  |  |
      +-----------+        +-----+-----+      +--+--+--+--+--+--+--+--+
        -> N_s                -> N_s               -> N_s

``stack``  = N_col = 1: D over all P — orthogonalization-friendly, but the
SpMV halo exchange spans all P processes (χ grows with N_row).
``pillar`` = N_row = 1: N_s over all P — every device holds all of D, the
filter's SpMV needs **no communication**, at the price of redistributing
V before/after each filter pass (Alg. 1 steps 7/9, ``redistribute.py``).
``panel``  = everything in between.

On a JAX mesh the horizontal layer maps to the ``row`` axis and the
vertical layer to the ``col`` axis (for the LM production mesh these are
the ``model`` / ``data`` axes; the multi-pod ``pod`` axis extends the
vertical layer — pods never communicate during the polynomial filter).
The χ-driven planner (``planner.py``) chooses between these layouts from
the sparsity pattern when ``FDConfig.layout == "auto"``.
"""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

__all__ = ["Layout", "stack", "pillar", "panel", "make_solver_mesh", "SOLVER_ROW", "SOLVER_COL"]

SOLVER_ROW = "row"  # horizontal layer (D)
SOLVER_COL = "col"  # vertical layer (N_s)


def make_solver_mesh(n_row: int, n_col: int, *, pods: int = 1, devices=None) -> Mesh:
    """Eigensolver mesh. With pods > 1 the pod axis multiplies the vertical
    layer (bundles of vectors across pods — zero SpMV communication).

    Axes are ``Auto``: the engines place their own collectives inside
    ``shard_map`` and leave the rest to GSPMD propagation."""
    if pods > 1:
        shape, axes = (pods, n_row, n_col), ("pod", SOLVER_ROW, SOLVER_COL)
    else:
        shape, axes = (n_row, n_col), (SOLVER_ROW, SOLVER_COL)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


@dataclasses.dataclass(frozen=True)
class Layout:
    """A distributed layout of the D x N_s vector matrix on a mesh."""

    name: str
    dist_axes: tuple[str, ...]  # mesh axes sharding the D axis
    bundle_axes: tuple[str, ...]  # mesh axes sharding the N_s axis

    def vec_pspec(self) -> P:
        """PartitionSpec for V of shape (D, N_s)."""
        return P(self.dist_axes or None, self.bundle_axes or None)

    def vec_sharding(self, mesh: Mesh) -> NamedSharding:
        return NamedSharding(mesh, self.vec_pspec())

    def n_row(self, mesh: Mesh) -> int:
        return _axes_size(mesh, self.dist_axes)

    def n_col(self, mesh: Mesh) -> int:
        return _axes_size(mesh, self.bundle_axes)

    def describe(self, mesh: Mesh) -> str:
        return f"{self.name}({self.n_row(mesh)}x{self.n_col(mesh)})"


def _axes_size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def stack(mesh: Mesh) -> Layout:
    """N_col = 1: D sharded over every mesh axis."""
    return Layout("stack", tuple(mesh.axis_names), ())


def pillar(mesh: Mesh) -> Layout:
    """N_row = 1: N_s sharded over every mesh axis (SpMV comm-free)."""
    return Layout("pillar", (), tuple(mesh.axis_names))


def panel(mesh: Mesh, row_axes=(SOLVER_ROW,), col_axes=None) -> Layout:
    """General N_row x N_col panel on the given mesh axes."""
    row_axes = tuple(row_axes)
    if col_axes is None:
        col_axes = tuple(a for a in mesh.axis_names if a not in row_axes)
    return Layout("panel", row_axes, tuple(col_axes))
