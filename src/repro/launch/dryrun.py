"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes and extract memory/cost/roofline data.

This is the proof that the distribution config is coherent without real
hardware: a sharding mismatch, compile-time OOM, or unsupported collective
fails the compile. Inputs are ShapeDtypeStructs — nothing is allocated.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out cache.json]
  PYTHONPATH=src python -m repro.launch.dryrun --eigen exciton200 --layout pillar
  PYTHONPATH=src python -m repro.launch.dryrun --eigen hubbard16 --layout panel+ov --plan
  PYTHONPATH=src python -m repro.launch.dryrun --eigen roadnet48k --layout panel \
      --spmv-comm compressed --plan
  PYTHONPATH=src python -m repro.launch.dryrun --eigen hubnet48k --layout panel \
      --spmv-comm compressed --spmv-schedule matching --plan
  PYTHONPATH=src python -m repro.launch.dryrun --eigen hubnet48k --layout panel \
      --spmv-comm compressed --spmv-schedule matching --spmv-balance commvol --plan
  PYTHONPATH=src python -m repro.launch.dryrun --eigen hubnet48k --layout panel \
      --spmv-sstep 2 --verify
  PYTHONPATH=src python -m repro.launch.dryrun --fit-machine --fit-out machine_fit.json
"""
import os
# a CPU compile rehearsal on 512 host devices: pin the CPU (an attached
# accelerator would otherwise be picked up) and keep the caller's flags
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse
import functools
import json
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from ..configs import ARCHS, EIGEN_CONFIGS, get_config
from ..models import decode as dec
from ..models import steps as steps_mod
from ..models import transformer as tfm
from ..models.config import ModelConfig, SHAPES, applicable_shapes
from ..optim import adamw
from . import roofline as rl
from .mesh import make_production_mesh
from .shardings import (batch_pspecs, decode_state_pspecs, dp_axes,
                        opt_pspecs, param_pspecs, to_shardings)


# ----------------------------------------------------------- input specs --

def batch_specs(cfg: ModelConfig, batch: int, seq: int):
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    S = jax.ShapeDtypeStruct
    dt = jnp.dtype(cfg.dtype)
    if cfg.family == "audio":
        return {
            "features": S((batch, seq, cfg.frontend_dim), dt),
            "mask": S((batch, seq), jnp.bool_),
            "labels": S((batch, seq), jnp.int32),
        }
    if cfg.family == "vlm":
        npfx = min(cfg.n_prefix_embeds, max(seq // 8, 1))
        return {
            "tokens": S((batch, seq - npfx), jnp.int32),
            "patches": S((batch, npfx, cfg.frontend_dim), dt),
            "labels": S((batch, seq - npfx), jnp.int32),
        }
    return {
        "tokens": S((batch, seq), jnp.int32),
        "labels": S((batch, seq), jnp.int32),
    }


def input_specs(arch: str, shape: str):
    """(cfg, cell, spec pytrees) for one dry-run cell."""
    cfg = get_config(arch)
    cell = SHAPES[shape]
    if cell.kind in ("train", "prefill"):
        return cfg, cell, batch_specs(cfg, cell.global_batch, cell.seq_len)
    return cfg, cell, None


def _params_shape(cfg):
    return jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))


def _model_flops(cfg: ModelConfig, cell) -> float:
    """MODEL_FLOPS: 6*N_active*D_tokens (train) / 2*N_active*D_tokens (fwd)."""
    n = cfg.n_active_params()
    tokens = cell.global_batch * (cell.seq_len if cell.kind != "decode" else 1)
    return (6.0 if cell.kind == "train" else 2.0) * n * tokens


# ------------------------------------------------------------- cell runs --

def lower_cell(arch: str, shape: str, mesh) -> tuple:
    """Build the jitted step for one cell and lower it on the mesh."""
    cfg, cell, batch = input_specs(arch, shape)
    pshape = _params_shape(cfg)
    pspec = param_pspecs(cfg, mesh, pshape)
    psh = to_shardings(mesh, pspec)
    if cell.kind == "train":
        ocfg = adamw.AdamWConfig(moment_dtype=cfg.optimizer_dtype)
        oshape = jax.eval_shape(functools.partial(adamw.init_state, ocfg), pshape)
        osh = to_shardings(mesh, opt_pspecs(cfg, mesh, oshape, pspec))
        bsh = to_shardings(mesh, batch_pspecs(cfg, mesh, batch))
        step = steps_mod.make_train_step(cfg, ocfg)
        jitted = jax.jit(step, in_shardings=(psh, osh, bsh),
                         out_shardings=(psh, osh, None),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(pshape, oshape, batch)
    elif cell.kind == "prefill":
        sshape = jax.eval_shape(functools.partial(
            dec.init_decode_state, cfg, cell.global_batch, cell.seq_len))
        ssh = to_shardings(mesh, decode_state_pspecs(cfg, mesh, sshape, cell.global_batch))
        bsh = to_shardings(mesh, batch_pspecs(cfg, mesh, batch))
        step = steps_mod.make_prefill_step(cfg, cell.seq_len)
        jitted = jax.jit(step, in_shardings=(psh, bsh), out_shardings=(None, ssh))
        lowered = jitted.lower(pshape, batch)
    else:  # decode: one new token against a seq_len-deep cache
        B = cell.global_batch
        sshape = jax.eval_shape(functools.partial(
            dec.init_decode_state, cfg, B, cell.seq_len))
        ssh = to_shardings(mesh, decode_state_pspecs(cfg, mesh, sshape, B))
        dp = dp_axes(mesh)
        tok_spec = batch_pspecs(cfg, mesh, {"t": jax.ShapeDtypeStruct((B,), jnp.int32)})["t"]
        tsh = to_shardings(mesh, tok_spec)
        step = steps_mod.make_decode_step(cfg)
        jitted = jax.jit(step, in_shardings=(psh, ssh, tsh, None),
                         out_shardings=(None, ssh), donate_argnums=(1,))
        lowered = jitted.lower(
            pshape, sshape, jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32))
    return cfg, cell, lowered


def run_cell(arch: str, shape: str, multi_pod: bool = False, verbose=True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    with mesh:
        cfg, cell, lowered = lower_cell(arch, shape, mesh)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = rl.memory_summary(compiled)
        roof = rl.analyze(compiled, _model_flops(cfg, cell), n_chips)
    rec = {
        "arch": arch, "shape": shape, "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips, "status": "ok",
        "t_lower_s": round(t_lower, 1), "t_compile_s": round(t_compile, 1),
        "memory": mem, "model_flops": _model_flops(cfg, cell),
        **roof.row(),
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape} on {rec['mesh']}: OK "
              f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s)")
        print(f"  memory_analysis: {mem}")
        print(f"  cost: flops/chip={roof.flops_per_chip:.3e} "
              f"bytes/chip={roof.hbm_bytes_per_chip:.3e} "
              f"coll bytes/chip={roof.coll_bytes_per_chip:.3e}")
        print(f"  roofline: compute={roof.t_compute*1e3:.2f}ms "
              f"memory={roof.t_memory*1e3:.2f}ms "
              f"collective={roof.t_collective*1e3:.2f}ms "
              f"dominant={roof.dominant} "
              f"useful={roof.useful_flops_ratio:.2f} "
              f"frac={roof.roofline_fraction:.3f}")
    return rec


# -------------------------------------------------- eigensolver dry-runs --

def run_eigen(name: str, layout_name: str = "pillar", multi_pod: bool = False,
              n_search: int | None = None, verbose=True,
              plan: bool = False, spmv_comm: str = "a2a",
              spmv_schedule: str = "cyclic", spmv_balance: str = "rows",
              spmv_reorder: str = "none", spmv_kernel: bool = False,
              spmv_sstep: int = 1, plan_mode: str = "auto",
              machine=None, verify: bool = False) -> dict:
    """Lower one FD macro-iteration (filter + redistributions + TSQR) for a
    paper config on the production mesh, using a reduced-bandwidth ELL
    surrogate with the *exact* χ-derived comm plan of the real matrix.

    ``layout_name`` may carry a ``+ov`` suffix (e.g. ``panel+ov``) to lower
    the split-phase overlap SpMV engine instead of the baseline; the record
    then also carries the overlap-aware perf-model prediction so the sweep
    can quantify when overlap restores scalability.

    ``spmv_comm="compressed"`` lowers the sparsity-compressed neighbor-
    permute engine instead of the padded all_to_all: the surrogate carries
    the real matrix's neighbor schedule (exact per-pair volumes where the
    pattern pass is affordable — CSR, small D, or finite ``reach`` — and
    the uniform χ-estimate otherwise), so the HLO-measured
    collective-permute volume is the engine's true wire footprint.
    ``spmv_schedule`` picks how those rounds are derived — ``"cyclic"``
    shifts (the ``+cmp`` cell suffix) or greedy ``"matching"`` rounds
    (``+mat``) — on the exact path; the estimated path always lowers the
    uniform cyclic rounds.

    ``spmv_balance``/``spmv_reorder`` lower the cell on a *planned* row
    partition (``core/partition.py``: commvol boundaries and/or the RCM
    row order, the ``+cv``/``+rcm`` cell suffixes): the surrogate then
    carries the planned map's exact per-pair volumes, so the
    HLO-measured bytes are the partitioned engine's true wire footprint.
    Requested partitions that cannot be planned (no halo at N_row = 1,
    or the per-row pattern pass unaffordable at this D) are relabeled
    back to ``rows``/``none`` so the record never claims a partition
    that did not lower.

    ``spmv_kernel=True`` requests the Pallas kernel engine (the ``+krn``
    cell suffix). The surrogate's plan arrays are ShapeDtypeStructs /
    tracers, so the host-side tile planner (``kernels/ops.py``) finds
    nothing concrete and the engine falls back to the jnp contraction by
    design — the lowered collectives (and hence every predicted ==
    measured check) are identical to the kernel-off cell, which is
    exactly the census contract the kernels must keep.

    ``spmv_sstep > 1`` lowers the communication-avoiding s-step filter
    cell (the ``+s2``/``+s3`` suffixes): the surrogate carries the real
    pattern's depth-s ghost plan (``comm_plan(..., sstep=s)``), the
    filter runs ⌈degree/s⌉ depth-s exchanges — a single-width seed
    exchange plus width-doubled ``[w1 | w2]`` group exchanges — and the
    ``--verify`` census attributes every one of them to the χ(A^s)
    terms of ``SpmvCommPlan.sstep_collectives``. s-step cells lower the
    plain (non-overlap) engine only, and need the exact pattern pass
    (requests it cannot honor are relabeled back to ``s = 1``).

    ``plan=True`` adds the χ-driven planner panel: the full candidate
    ranking (``core/planner.py``) for this matrix on the production mesh,
    plus the predicted SpMV collective volume of the lowered cell next to
    the HLO-measured one — prediction and measurement in one place; on a
    planned partition it also prints the before/after χ and pad volumes
    of the re-balanced rows."""
    from ..configs import get_config as gc
    from ..core import layouts as L
    from ..core.filter_diag import FDConfig
    from ..core import spmv as spmv_mod
    from ..core.orthogonalize import make_tsqr
    from ..core.redistribute import make_redistribute
    from ..core.chebyshev import chebyshev_filter
    from ..matrices import get_family

    conf = gc(name)
    fd: FDConfig = conf["fd"]
    overlap = layout_name.endswith("+ov")
    if overlap:
        layout_name = layout_name[:-3]
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = mesh.axis_names
    # map the solver layers onto the production mesh:
    #   horizontal (D) -> "model", vertical (bundles) -> "data" (+"pod")
    col_axes = tuple(a for a in axes if a != "model")
    if layout_name == "stack":
        panel_l = L.Layout("panel", ("model",) + col_axes, ())
    elif layout_name == "pillar":
        panel_l = L.Layout("pillar", (), ("model",) + col_axes)
    else:
        panel_l = L.Layout("panel", ("model",), col_axes)
    stack_l = L.Layout("stack", panel_l.dist_axes + panel_l.bundle_axes, ())
    mspec = dict(conf["matrix"])
    fam = get_family(mspec.pop("family"), **mspec)
    D = fam.D
    P_total = mesh.devices.size
    N_row = panel_l.n_row(mesh)
    n_s = n_search or fd.n_search
    # pad N_s to the bundle count
    n_col = panel_l.n_col(mesh)
    n_s = -(-n_s // max(n_col, 1)) * max(n_col, 1)
    dt = jnp.complex64 if fam.is_complex else jnp.float32

    # planned row partition of the cell (core/partition.py): the map is
    # planned at the cell's N_row with block_multiple = P_total/N_row so
    # its padded extent divides the full mesh (TSQR + redistribution run
    # at P_total). Unplannable requests are relabeled to rows/none.
    from ..core.partition import partition_plan_default, plan_rowmap

    rowmap = None
    use_sampled = plan_mode == "sampled" or (
        plan_mode == "auto" and not partition_plan_default(fam, N_row))
    if (spmv_balance, spmv_reorder) != ("rows", "none") and N_row > 1 \
            and partition_plan_default(fam, N_row, plan_mode) \
            and not (use_sampled and spmv_reorder != "none"):
        # sampled planning covers the commvol axis only (RCM needs the
        # full adjacency) — unplannable requests relabel below as usual
        rowmap = plan_rowmap(fam, N_row, balance=spmv_balance,
                             reorder=spmv_reorder,
                             block_multiple=P_total // N_row,
                             plan_mode=plan_mode)
        if rowmap.identity:
            rowmap = None
    if rowmap is None:
        spmv_balance, spmv_reorder = "rows", "none"
    D_pad = rowmap.D_pad if rowmap is not None \
        else -(-D // P_total) * P_total

    # surrogate distributed operator: exact comm plan (χ-padded all_to_all
    # or the compressed neighbor schedule) on a bandwidth-matched synthetic
    # ELL. Only ShapeDtypeStructs are built — the plan arrays are jit
    # *arguments*, nothing is allocated.
    from ..core.planner import comm_plan as _comm_plan
    from ..core.planner import exact_comm_default

    cp_part = None
    if rowmap is not None:
        if use_sampled and not exact_comm_default(fam):
            # the exact mapped pattern pass is exactly what sampled mode
            # avoids — estimate the planned map's volumes the same way
            from ..core.sketch import sampled_comm_plan
            cp_part = sampled_comm_plan(fam, N_row, rowmap=rowmap)
        else:
            cp_part = _comm_plan(fam, N_row, rowmap=rowmap)
        n_vc = cp_part.n_vc
    else:
        n_vc = fam.n_vc(np.minimum(np.arange(N_row + 1) * (D_pad // N_row), D)) if N_row > 1 else np.zeros(1)
    t0 = time.time()
    W = int(round(_nnzr(fam)))
    R = D_pad // N_row
    if N_row <= 1:
        L = 1
    elif cp_part is not None:
        L = max(cp_part.L, 1)  # the planned partition's exact pair max
    else:
        L = max(-(-int(n_vc.max()) // max(N_row - 1, 1)), 1)
    # overlap surrogate: split the width budget into local + halo parts
    # (halo rows ~ ceil(n_vc / R) entries wide on average)
    W_halo = max(1, -(-int(n_vc.max()) // max(R, 1))) if N_row > 1 else 1
    W_loc = max(1, W - W_halo)
    compressed = spmv_comm == "compressed" and N_row > 1
    perms, round_L = (), ()
    cp_nbr = None
    if compressed:
        # neighbor schedule of the real pattern: exact per-pair volumes
        # when the pattern pass is affordable, uniform χ-estimate rounds
        # otherwise (the prediction below always uses THIS schedule, so
        # predicted == measured stays exact either way). On a planned
        # partition the schedule comes from the planned map's own counts.
        if cp_part is not None:
            cp_nbr = cp_part
            perms, round_L = cp_nbr.permute_schedule(spmv_schedule)
        elif exact_comm_default(fam):
            cp_nbr = _comm_plan(fam, N_row, d_pad=D_pad, exact=True)
            perms, round_L = cp_nbr.permute_schedule(spmv_schedule)
        else:
            # without per-pair counts only the uniform cyclic rounds can
            # be lowered — relabel so the cell/record never claim a
            # matching engine that did not run
            spmv_schedule = "cyclic"
            perms = tuple(tuple((j, (j + k) % N_row) for j in range(N_row))
                          for k in range(1, N_row))
            round_L = (L,) * (N_row - 1)
    H = int(sum(round_L))

    # communication-avoiding s-step cell (the +s2/+s3 suffixes): the
    # surrogate carries the real pattern's depth-s ghost plan so the
    # lowered ⌈degree/s⌉ exchanges (one single-width seed + width-doubled
    # group payloads) are the engine's true wire footprint. Plain engine
    # only; requests the exact pattern pass cannot honor fall back to
    # the per-step cell so the record never claims an s that did not
    # lower.
    sstep = max(int(spmv_sstep), 1)
    if sstep > 1 and overlap:
        raise ValueError("s-step dry-run cells lower the plain engine "
                         "only (drop the '+ov' layout suffix)")
    if sstep > 1 and N_row <= 1:
        sstep = 1  # comm-free layout: every s is the same cell
    if sstep > 1 and not exact_comm_default(fam):
        # depth-s ghosts need the exact pattern pass whether or not a
        # partition was planned (a sampled rowmap does not change that)
        if verbose:
            print(f"[dryrun-eigen] {name}: depth-{sstep} ghost plan needs "
                  "the exact pattern pass — relabeling to s=1")
        sstep = 1
    cp_s = None
    G_s = L_s = 0
    perms_s, round_L_s = (), ()
    if sstep > 1:
        cp_s = (_comm_plan(fam, N_row, rowmap=rowmap, sstep=sstep)
                if rowmap is not None
                else _comm_plan(fam, N_row, d_pad=D_pad, sstep=sstep))
        G_s = int(cp_s.n_vc.max())
        L_s = int(cp_s.L)
        if G_s == 0:
            sstep, cp_s = 1, None  # no halo at this split
        elif compressed:
            perms_s, round_L_s = cp_s.permute_schedule(spmv_schedule)
            perms, round_L = perms_s, round_L_s  # the lowered schedule
            H = int(sum(round_L_s))

    ell_spec = dict(
        cols=jax.ShapeDtypeStruct((N_row, R, W), jnp.int32),
        vals=jax.ShapeDtypeStruct((N_row, R, W), dt),
        send_idx=jax.ShapeDtypeStruct((N_row, N_row, L), jnp.int32),
        cols_loc=jax.ShapeDtypeStruct((N_row, R, W_loc), jnp.int32),
        vals_loc=jax.ShapeDtypeStruct((N_row, R, W_loc), dt),
        cols_halo=jax.ShapeDtypeStruct((N_row, R, W_halo), jnp.int32),
        vals_halo=jax.ShapeDtypeStruct((N_row, R, W_halo), dt),
        send_nbr=jax.ShapeDtypeStruct((N_row, max(H, 1)), jnp.int32),
    )
    tsqr = make_tsqr(mesh, stack_l)
    to_panel, to_stack = make_redistribute(mesh, stack_l, panel_l)
    degree = 32

    # one surrogate body per engine combination; plan arrays arrive as jit
    # arguments and are planted pre-split (and pre-scheduled) on the
    # DistEll so the device code never materializes host data from tracers
    def make_nbr(send_nbr, cols_nbr, cols_halo_nbr):
        plan = spmv_mod.NeighborPlan(perms=perms, round_L=round_L,
                                     send_nbr=send_nbr, cols_nbr=cols_nbr,
                                     cols_halo_nbr=cols_halo_nbr)
        return {spmv_schedule: plan}

    def fd_iteration(V, mu, alpha, beta, cols, vals, send_idx, send_nbr):
        nbr = make_nbr(send_nbr, cols, cols) if compressed else None
        ell = spmv_mod.DistEll(cols=cols, vals=vals, send_idx=send_idx,
                               R=R, L=L, P=N_row, D=D, nbr=nbr)
        spmv = spmv_mod.make_spmv(mesh, panel_l, ell, comm=spmv_comm,
                                  schedule=spmv_schedule,
                                  use_kernel=spmv_kernel)
        Q, _ = tsqr(V)
        Vp = to_panel(Q)
        Vp = chebyshev_filter(spmv, mu, alpha, beta, Vp)
        return to_stack(Vp)

    def fd_iteration_ov(V, mu, alpha, beta, cols_loc, vals_loc, cols_halo,
                        vals_halo, send_idx, send_nbr):
        nbr = make_nbr(send_nbr, cols_loc, cols_halo) if compressed else None
        ell = spmv_mod.DistEll(cols=cols_loc, vals=vals_loc, send_idx=send_idx,
                               R=R, L=L, P=N_row, D=D,
                               cols_loc=cols_loc, vals_loc=vals_loc,
                               cols_halo=cols_halo, vals_halo=vals_halo,
                               nbr=nbr)
        spmv = spmv_mod.make_spmv(mesh, panel_l, ell, overlap=True,
                                  comm=spmv_comm, schedule=spmv_schedule,
                                  use_kernel=spmv_kernel)
        Q, _ = tsqr(V)
        Vp = to_panel(Q)
        Vp = chebyshev_filter(spmv, mu, alpha, beta, Vp)
        return to_stack(Vp)

    def fd_iteration_ss(V, mu, alpha, beta, ex_a, ex_b, *steps_flat):
        # depth-s surrogate: the per-step ELL blocks and the exchange
        # plan arrive as jit arguments; the compressed schedule's host
        # rounds (perms_s/round_L_s) are planted so neighbor_plan never
        # touches tracer pair counts
        steps = tuple((steps_flat[2 * i], steps_flat[2 * i + 1])
                      for i in range(sstep))
        nbr = None
        if compressed:
            nbr = {spmv_schedule: spmv_mod.SstepNeighbor(
                perms=perms_s, round_L=round_L_s,
                send_nbr=ex_a, gather=ex_b)}
        sell = spmv_mod.SstepEll(steps=steps, send_idx=ex_a, gather_a2a=ex_b,
                                 R=R, G=G_s, L=L_s, P=N_row, D=D, s=sstep,
                                 nbr=nbr)
        cheb = spmv_mod.make_sstep_cheb(mesh, panel_l, sell,
                                        comm=spmv_comm,
                                        schedule=spmv_schedule,
                                        use_kernel=spmv_kernel)
        Q, _ = tsqr(V)
        Vp = to_panel(Q)
        Vp = cheb(Vp, mu, alpha, beta)
        return to_stack(Vp)

    V = jax.ShapeDtypeStruct((D_pad, n_s), dt)
    mu = jax.ShapeDtypeStruct((degree + 1,), jnp.float32)
    dist = panel_l.dist_axes
    from jax.sharding import PartitionSpec as PS
    plan_sh = jax.NamedSharding(mesh, PS(dist if dist else None, None, None))
    send_sh = jax.NamedSharding(mesh, PS(dist if dist else None, None))
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    with mesh:
        vsh = jax.NamedSharding(mesh, stack_l.vec_pspec())
        if sstep > 1:
            S = jax.ShapeDtypeStruct
            if compressed:
                ex_specs = (S((N_row, max(H, 1)), jnp.int32),
                            S((N_row, G_s), jnp.int32))
                ex_sh = (send_sh, send_sh)
            else:
                ex_specs = (S((N_row, N_row, L_s), jnp.int32),
                            S((N_row, G_s), jnp.int32))
                ex_sh = (plan_sh, send_sh)
            step_specs = tuple(
                spec for _ in range(sstep)
                for spec in (S((N_row, R + G_s, W), jnp.int32),
                             S((N_row, R + G_s, W), dt)))
            jitted = jax.jit(fd_iteration_ss,
                             in_shardings=(vsh, None, None, None) + ex_sh
                             + (plan_sh,) * (2 * sstep),
                             out_shardings=vsh, donate_argnums=(0,))
            lowered = jitted.lower(V, mu, scalar, scalar,
                                   *ex_specs, *step_specs)
        elif overlap:
            jitted = jax.jit(fd_iteration_ov,
                             in_shardings=(vsh, None, None, None)
                             + (plan_sh,) * 5 + (send_sh,),
                             out_shardings=vsh, donate_argnums=(0,))
            lowered = jitted.lower(V, mu, scalar, scalar,
                                   ell_spec["cols_loc"], ell_spec["vals_loc"],
                                   ell_spec["cols_halo"], ell_spec["vals_halo"],
                                   ell_spec["send_idx"], ell_spec["send_nbr"])
        else:
            jitted = jax.jit(fd_iteration,
                             in_shardings=(vsh, None, None, None,
                                           plan_sh, plan_sh, plan_sh, send_sh),
                             out_shardings=vsh, donate_argnums=(0,))
            lowered = jitted.lower(V, mu, scalar, scalar,
                                   ell_spec["cols"], ell_spec["vals"],
                                   ell_spec["send_idx"], ell_spec["send_nbr"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = rl.memory_summary(compiled)
        # useful flops: degree SpMVs (2*nnz*n_s) + TSQR (2*D*Ns^2)
        nnz = fam.D * _nnzr(fam)
        useful = degree * 2.0 * nnz * n_s * (4 if fam.is_complex else 1) \
            + 2.0 * D * n_s * n_s
        roof = rl.analyze(compiled, useful, mesh.devices.size)
    cmp_tag = ("" if not compressed
               else "+mat" if spmv_schedule == "matching" else "+cmp")
    part_tag = ("+cv" if spmv_balance == "commvol" else "") + \
        ("+rcm" if spmv_reorder == "rcm" else "")
    krn_tag = "+krn" if spmv_kernel else ""
    ss_tag = f"+s{sstep}" if sstep > 1 else ""
    rec = {
        "arch": name,
        "shape": (f"fd_iter[{layout_name}{part_tag}{cmp_tag}"
                  f"{'+ov' if overlap else ''}{krn_tag}{ss_tag},"
                  f"Ns={n_s},deg={degree}]"),
        "mesh": "2x16x16" if multi_pod else "16x16", "n_chips": mesh.devices.size,
        "status": "ok", "t_lower_s": round(t_lower, 1),
        "t_compile_s": round(t_compile, 1), "memory": mem,
        "model_flops": useful, **roof.row(),
        "chi_comm_plan_L": int(L), "n_vc_max": int(n_vc.max()) if N_row > 1 else 0,
        "spmv_comm": spmv_comm, "spmv_schedule": spmv_schedule,
        "spmv_balance": spmv_balance, "spmv_reorder": spmv_reorder,
        "spmv_kernel": spmv_kernel, "spmv_sstep": sstep,
        "nbr_H": H, "nbr_rounds": len(perms),
    }
    if sstep > 1:
        rec["sstep_L"] = L_s
        rec["sstep_ghosts_max"] = G_s
        rec["sstep_groups"] = cp_s.n_groups(degree)
        rec["sstep_work_factor"] = round(cp_s.sstep_work_factor(), 4)
    if verify:
        # static communication verifier (repro.analysis): attribute every
        # collective in the compiled HLO to a χ-predicted term and lint
        # the lowered neighbor schedule. The dry-run cell has no Gram
        # product, so the predicted terms are degree halo exchanges + the
        # TSQR butterfly + (when N_col > 1) the two redistributions.
        from ..analysis.census import ExpectedTerm, attribute
        from ..analysis.plan_lint import lint_rounds
        from .hlo_analysis import collective_census

        S_cell = jnp.dtype(dt).itemsize
        n_b_cell = max(n_s // max(n_col, 1), 1)
        terms = []
        if sstep > 1:
            # χ(A^s) attribution: one single-width seed exchange plus
            # ⌈degree/s⌉ - 1 width-doubled group exchanges, already
            # whole-filter terms (sstep_collectives is NOT degree-scaled)
            for k, (kind, byts, cnt) in enumerate(cp_s.sstep_collectives(
                    spmv_comm, spmv_schedule, n_b_cell, S_cell, degree)):
                terms.append(ExpectedTerm(
                    f"sstep-exchange[{spmv_comm}/s{sstep}#{k}]",
                    kind, int(byts), cnt))
        elif N_row > 1 and L > 0:
            if compressed:
                for Lk in round_L:
                    terms.append(ExpectedTerm(
                        f"halo-exchange[compressed/{spmv_schedule}]",
                        "collective-permute",
                        int(Lk) * n_b_cell * S_cell, degree))
            else:
                terms.append(ExpectedTerm(
                    "halo-exchange[a2a]", "all-to-all",
                    N_row * L * n_b_cell * S_cell, degree))
        if P_total > 1:
            terms.append(ExpectedTerm(
                "tsqr-butterfly", "collective-permute", n_s * n_s * S_cell,
                int(np.ceil(np.log2(P_total)))))
        if n_col > 1:
            full = (D_pad // P_total) * n_s * S_cell
            for leg in ("to_panel", "to_stack"):
                terms.append(ExpectedTerm(
                    f"redistribute[{leg}]", "all-to-all", full, 1,
                    alt_bytes=(full * (n_col - 1) // n_col,)))
        extra = []
        if sstep > 1 and perms_s:
            # lint the depth-s rounds against the depth-s pair volumes
            extra = lint_rounds(cp_s.pair_counts, perms_s, round_L_s,
                                label=f"{name}/{spmv_schedule}+s{sstep}")
        elif cp_nbr is not None and cp_nbr.pair_counts is not None and perms:
            extra = lint_rounds(cp_nbr.pair_counts, perms, round_L,
                                label=f"{name}/{spmv_schedule}")
        report = attribute(collective_census(compiled.as_text()), terms,
                           cell=rec["shape"], extra_errors=list(extra))
        rec["verify_ok"] = report.ok
        rec["verify_errors"] = report.errors
        if verbose or not report.ok:
            print(report.describe())
    if rowmap is not None:
        sizes = rowmap.block_sizes(N_row)
        rec["partition_rows_min"] = int(sizes.min())
        rec["partition_rows_max"] = int(sizes.max())
    if compressed:
        # round-sum comm prediction of the lowered schedule (identical to
        # the χ-path by construction — perf_model.schedule_comm_time),
        # priced on the same machine model the --plan ranking uses
        from ..core import perf_model as _pmsc

        rec["t_comm_schedule_s"] = _pmsc.schedule_comm_time(
            machine or _pmsc.TPU_V5E, round_L, n_b=n_s // max(n_col, 1),
            S_d=jnp.dtype(dt).itemsize)
    # perf-model per-Chebyshev-iteration prediction for this cell: additive
    # Eq. 12 vs the overlap engine's max(T_comm, T_local) + T_halo — the
    # sweep uses the ratio to see where overlap restores scalability
    if N_row > 1:
        from ..core import perf_model as pm
        from ..core.metrics import chi_from_nvc

        if rowmap is not None:
            n_vm = rowmap.block_sizes(N_row)
        else:
            bnd = np.minimum(np.arange(N_row + 1) * (D_pad // N_row), D)
            n_vm = np.diff(bnd)
        chim = chi_from_nvc(n_vc, n_vm, D)
        n_b_loc = max(n_s // max(n_col, 1), 1)
        kw = dict(D=D, N_p=N_row, n_b=n_b_loc, chi=chim.chi1,
                  n_nzr=_nnzr(fam), S_d=jnp.dtype(dt).itemsize)
        rec["t_model_additive_s"] = pm.cheb_iter_time(pm.TPU_V5E, **kw)
        rec["t_model_overlap_s"] = pm.cheb_iter_time_overlap(pm.TPU_V5E, **kw)
        rec["overlap_model_speedup"] = round(
            rec["t_model_additive_s"] / rec["t_model_overlap_s"], 3)
    if plan:
        # planner panel: ranking from the pattern alone + predicted vs
        # measured all-to-all volume of this lowered cell (χ is the
        # prediction; the HLO parse is the measurement)
        from ..core.planner import plan_for_mesh
        from ..core.redistribute import redistribution_volume

        P_t = mesh.devices.size
        S_cell = jnp.dtype(dt).itemsize
        from ..core import perf_model as _pm
        from ..core.planner import exact_comm_default

        # exact pair counts (and hence compressed candidates) whenever the
        # pattern pass is windowed/cheap; unbounded generators at paper
        # scale reuse the n_vc already computed above (estimated path —
        # the planner then only ranks the a2a engines, by design). A
        # comm plan already built for the compressed schedule is handed
        # through so the lowered n_row's pattern pass is never paid twice
        exact_ok = exact_comm_default(fam)
        # precomputed plans/counts only describe the equal-rows partition
        # — never hand the planned map's counts to the rows combo
        lp = plan_for_mesh(fam, mesh, n_search=n_s, row_axes=("model",),
                           degree=degree, S_d=S_cell,
                           exact_comm=None if exact_ok else False,
                           d_pad=D_pad, n_nzr=_nnzr(fam),
                           machine=machine or _pm.TPU_V5E,
                           plan_mode=plan_mode,
                           reorder=tuple(dict.fromkeys(
                               ("none", spmv_reorder))),
                           sstep=tuple(dict.fromkeys((1, sstep))),
                           comm_plan_by_row=None
                           if cp_nbr is None or rowmap is not None
                           else {N_row: cp_nbr},
                           n_vc_by_row=None
                           if exact_ok or N_row <= 1 or rowmap is not None
                           else {N_row: n_vc})
        if rowmap is not None and exact_ok:
            # before/after panel: the equal-rows partition's χ and pad
            # volumes vs the planned map's, at the lowered N_row (needs
            # the exact pattern pass — skipped on the sampled-only path)
            cp_before = _comm_plan(fam, N_row,
                                   d_pad=-(-D // P_total) * P_total,
                                   exact=True)
            for tag, cp_x in (("before", cp_before), ("after", cp_part)):
                chim_x = cp_x.chi
                rec[f"partition_{tag}"] = {
                    "chi1": round(chim_x.chi1, 4),
                    "chi2": round(chim_x.chi2, 4),
                    "chi3": round(chim_x.chi3, 4),
                    "a2a_pad_entries": cp_x.moved_entries_per_device("a2a"),
                    "H_cyclic": cp_x.moved_entries_per_device(
                        "compressed", "cyclic"),
                    "H_matching": cp_x.moved_entries_per_device(
                        "compressed", "matching"),
                }
            if verbose:
                b, a = rec["partition_before"], rec["partition_after"]
                print(f"[plan] partition {spmv_balance}/{spmv_reorder} "
                      f"before -> after at N_row={N_row}:")
                print(f"       chi2 {b['chi2']:.4f} -> {a['chi2']:.4f}  "
                      f"chi3 {b['chi3']:.4f} -> {a['chi3']:.4f}")
                print(f"       pad entries/device: a2a "
                      f"{b['a2a_pad_entries']} -> {a['a2a_pad_entries']}  "
                      f"cyclic {b['H_cyclic']} -> {a['H_cyclic']}  "
                      f"matching {b['H_matching']} -> {a['H_matching']}")
        # predicted per-chip SpMV collective operand bytes of THIS cell:
        # degree halo exchanges — the [N_row, L, n_b] all_to_all send
        # buffer, or the compressed engine's Σ_k L_k ppermute segments —
        # plus 2 redistributions (full local slice; Eq. 17/18 is the moved
        # subset — XLA prints either convention, so report both)
        n_b_cell = n_s // max(n_col, 1)
        if sstep > 1:
            # whole-filter depth-s exchange bytes (seed + doubled groups)
            pred_spmv = sum(b * c for _, b, c in cp_s.sstep_collectives(
                spmv_comm, spmv_schedule, n_b_cell, S_cell, degree))
        else:
            spmv_entries = (H if compressed else N_row * L) \
                if N_row > 1 else 0
            pred_spmv = degree * spmv_entries * n_b_cell * S_cell
        # TSQR butterfly: log2(P) ppermute rounds of the N_s x N_s R factor
        # (orthogonalize.py) — counted with the SpMV permutes by the HLO
        # parse, so predict it too
        pred_tsqr = P_t.bit_length() - 1 if P_t & (P_t - 1) == 0 \
            else int(np.ceil(np.log2(P_t)))
        pred_tsqr *= n_s * n_s * S_cell
        pred_red_full = 2 * (D_pad // P_t) * n_s * S_cell if n_col > 1 else 0
        pred_red_moved = 2 * int(redistribution_volume(
            D_pad, n_s, P_t, n_col, S_cell)["bytes_total"] / P_t) \
            if n_col > 1 else 0
        meas_a2a = int(roof.coll_breakdown.get("all-to-all", 0))
        meas_perm = int(roof.coll_breakdown.get("collective-permute", 0))
        # the compressed engine's SpMV bytes are collective-permutes; the
        # redistribution stays an all_to_all — sum both kinds so the
        # predicted==measured check covers every engine. Two honest
        # conventions for the redistribution operand (XLA may print the
        # full local slice or only the moved subset) — report BOTH ratios;
        # agreement means one of them is ~1, and the spmv term (the χ
        # prediction proper) is identical in both
        meas = meas_a2a + meas_perm
        pred_full = pred_spmv + pred_tsqr + pred_red_full
        pred_moved = pred_spmv + pred_tsqr + pred_red_moved
        rec["plan_best"] = lp.best.describe()
        rec["plan_chi1"] = lp.best.chi1
        rec["plan_pred_spmv_bytes"] = pred_spmv
        rec["plan_pred_a2a_bytes_full"] = pred_full
        rec["plan_pred_a2a_bytes_moved"] = pred_moved
        rec["plan_measured_a2a_bytes"] = meas_a2a
        rec["plan_measured_permute_bytes"] = meas_perm
        if verbose:
            print(lp.report())
            r_full = meas / pred_full if pred_full else float("nan")
            r_moved = meas / pred_moved if pred_moved else float("nan")
            kind = "permute" if compressed else "a2a"
            print(f"[plan] cell spmv({kind})/chip predicted: {degree}x"
                  f"{pred_spmv // max(degree, 1)} + tsqr {pred_tsqr} "
                  f"+ redist(full) {pred_red_full} = {pred_full} | "
                  f"redist(moved) {pred_red_moved} = {pred_moved}  measured "
                  f"a2a {meas_a2a} + permute {meas_perm}  "
                  f"ratio full {r_full:.3f} / moved {r_moved:.3f}")
    if verbose:
        print(f"[dryrun-eigen] {name} "
              f"[{layout_name}{part_tag}{cmp_tag}"
              f"{'+ov' if overlap else ''}{krn_tag}{ss_tag}] "
              f"on {rec['mesh']}: OK "
              f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s)")
        if "overlap_model_speedup" in rec:
            print(f"  perf model/iter: additive={rec['t_model_additive_s']*1e3:.2f}ms "
                  f"overlap={rec['t_model_overlap_s']*1e3:.2f}ms "
                  f"(x{rec['overlap_model_speedup']:.2f} if overlapped)")
        print(f"  memory_analysis: {mem}")
        print(f"  roofline: compute={roof.t_compute*1e3:.2f}ms "
              f"memory={roof.t_memory*1e3:.2f}ms "
              f"collective={roof.t_collective*1e3:.2f}ms dominant={roof.dominant}")
    return rec


def _nnzr(fam) -> float:
    probe = np.arange(0, min(fam.D, 4096), dtype=np.int64)
    r, _ = fam.row_cols(probe)
    return len(r) / len(probe)


# -------------------------------------------------- machine-model fitting --

def fit_machine(eigen: str | None = None, out_path: str = "machine_fit.json",
                n_devices: int = 8, n_search: int = 16, reps: int = 20,
                verbose: bool = True):
    """Calibrate the planner's machine constants from *measured* dry-run
    iteration times (ROADMAP "feed measured dry-run times back").

    Runs the real fused Chebyshev step (baseline a2a engine) of a small
    matrix instance across several mesh splits on ``n_devices`` local
    devices, times each, and least-squares fits b_c, κ and the
    per-round launch latency α via ``MachineModel.fit`` (b_m is kept
    from the TPU_V5E base — the paper fixes b_m from STREAM and fits
    the rest the same way). Every sample carries its collective round
    count (one a2a per fused step when N_row > 1), and each halo split
    is timed twice — at the full block width and at a *tiny* width-n_col
    block whose wire bytes are negligible but whose round count is
    unchanged — so the α column is not collinear with the χ·bytes
    column and the latency term is identifiable (see
    ``MachineModel.fit``). The fitted model is saved as JSON for
    ``solve --machine <path>`` / ``dryrun --plan --machine <path>``, so
    planner rankings (including the s-step axis, which only wins under
    high α) can use calibrated constants instead of the hardcoded
    MEGGIE/TPU_V5E numbers.
    """
    from ..core import perf_model as pm
    from ..core import spmv as spmv_mod
    from ..core.layouts import make_solver_mesh, panel, stack
    from ..core.planner import comm_plan, estimate_nnzr
    from ..matrices import SpinChainXXZ, get_family

    if eigen:
        mspec = dict(get_smoke_matrix(eigen))
        fam = get_family(mspec.pop("family"), **mspec)
    else:
        fam = SpinChainXXZ(12, 6)
    csr = fam.build_csr()
    D = csr.shape[0]
    n_nzr = estimate_nnzr(csr)
    S_d = None  # set from the dtype the engine actually runs (see below)
    devices = jax.devices()[:n_devices]
    samples = []
    base = pm.TPU_V5E
    if verbose:
        print(f"[fit-machine] timing {fam.describe()} fused Chebyshev steps "
              f"on {n_devices} devices")
    splits = sorted({n for n in (n_devices, n_devices // 2, n_devices // 4)
                     if n >= 1}, reverse=True)
    for n_row in splits:
        n_col = n_devices // n_row
        if n_search % n_col:
            continue
        mesh = make_solver_mesh(n_row, n_col, devices=devices)
        lay = stack(mesh) if n_col == 1 else panel(mesh)
        D_pad = -(-D // n_devices) * n_devices
        ell = spmv_mod.build_dist_ell(csr, n_row, d_pad=D_pad)
        # Eq. 12's S_d must describe the elements the timed engine moves:
        # without jax_enable_x64 (this module never sets it) the operator
        # and vectors run in float32/complex64, not the host float64
        S_d = int(ell.vals.dtype.itemsize)
        cp = comm_plan(csr, n_row, d_pad=D_pad)
        chi_eng = pm.engine_chi(cp.moved_entries_per_device("a2a"), D, n_row)
        rng = np.random.default_rng(0)
        W1 = np.zeros((D_pad, n_search))
        W1[:D] = rng.standard_normal((D, n_search))
        W2 = np.zeros_like(W1)
        W2[:D] = rng.standard_normal((D, n_search))
        rounds = 1.0 if n_row > 1 else 0.0  # one a2a per fused step
        with mesh:
            sh = lay.vec_sharding(mesh)
            step = jax.jit(spmv_mod.make_fused_cheb_step(mesh, lay, ell))
            # full-width cell + (on halo splits) a tiny width-n_col cell:
            # same round count, negligible wire bytes — the contrast that
            # makes the α latency column identifiable
            widths = [n_search] + ([n_col] if n_row > 1 else [])
            for width in widths:
                w1 = jax.device_put(jnp.asarray(W1[:, :width]), sh)
                w2 = jax.device_put(jnp.asarray(W2[:, :width]), sh)
                y = step(w1, w2, 0.7, -0.2)
                jax.block_until_ready(y)  # compile outside the timing
                t0 = time.perf_counter()
                for _ in range(reps):
                    y = step(w1, w2, 0.7, -0.2)
                jax.block_until_ready(y)
                t = (time.perf_counter() - t0) / reps
                samples.append(dict(t=t, D=D, N_p=n_row,
                                    n_b=width // n_col, chi=chi_eng,
                                    n_nzr=n_nzr, S_d=S_d, rounds=rounds))
                if verbose:
                    print(f"[fit-machine] {n_row}x{n_col} n_b="
                          f"{width // n_col}: chi_eng={chi_eng:.3f} "
                          f"rounds={rounds:g} t={t * 1e6:.1f}us")
    fitted = pm.MachineModel.fit(samples, b_m=base.b_m, name="fitted-local")
    pm.save_machine(fitted, out_path)
    if verbose:
        bc = fitted.b_c / 1e9 if fitted.b_c != float("inf") else float("inf")
        print(f"[fit-machine] fitted b_c={bc:.2f} GB/s kappa={fitted.kappa:.2f} "
              f"alpha={fitted.alpha*1e6:.2f}us "
              f"(b_m fixed at {fitted.b_m/1e9:.0f} GB/s) -> {out_path}")
    return fitted


def get_smoke_matrix(eigen: str) -> dict:
    """Matrix spec of a config's reduced SMOKE instance (fit-machine runs
    real iterations, so the full paper-scale instance is out of reach)."""
    from ..configs import get_smoke_config

    return get_smoke_config(eigen)["matrix"]


# ------------------------------------------------------------------ main --

def iter_cells():
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape, cell in applicable_shapes(cfg).items():
            yield arch, shape, cell


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--eigen", help="paper config dry-run (exciton200/"
                                    "hubbard16/roadnet48k/hubnet48k)")
    ap.add_argument("--layout", default="pillar",
                    choices=["stack", "panel", "pillar", "panel+ov", "stack+ov"],
                    help="eigensolver vector layout for --eigen cells; the "
                         "'+ov' suffix lowers the split-phase overlap SpMV "
                         "engine (halo all_to_all issued before the local "
                         "contraction — the --spmv-overlap flag of "
                         "repro.launch.solve)")
    ap.add_argument("--spmv-comm", default="a2a",
                    choices=["a2a", "compressed"],
                    help="halo-exchange engine for --eigen cells: 'a2a' "
                         "(padded all_to_all, chi3-scaled bytes) or "
                         "'compressed' (neighbor ppermute rounds with "
                         "per-round padding, chi2-scaled bytes — the "
                         "'+cmp' shape suffix; --spmv-comm of "
                         "repro.launch.solve)")
    ap.add_argument("--spmv-schedule", default="cyclic",
                    choices=["cyclic", "matching"],
                    help="round scheduler of the compressed halo "
                         "exchange for --eigen cells: 'cyclic' (one "
                         "ppermute round per nonzero cyclic shift) or "
                         "'matching' (greedy max-weight matching "
                         "rounds, the '+mat' shape suffix; "
                         "--spmv-schedule of repro.launch.solve)")
    ap.add_argument("--spmv-balance", default="rows",
                    choices=["rows", "commvol"],
                    help="row partition for --eigen cells: 'rows' (equal "
                         "blocks) or 'commvol' (planned non-uniform "
                         "boundaries, core/partition.py — the '+cv' cell "
                         "suffix; the surrogate carries the planned "
                         "map's exact per-pair volumes)")
    ap.add_argument("--spmv-reorder", default="none",
                    choices=["none", "rcm"],
                    help="row order for --eigen cells: 'none' or 'rcm' "
                         "(reverse-Cuthill-McKee, applied before "
                         "partitioning — the '+rcm' cell suffix)")
    ap.add_argument("--spmv-kernel", action="store_true",
                    help="request the Pallas kernel engine for --eigen "
                         "cells (the '+krn' cell suffix; --spmv-kernel of "
                         "repro.launch.solve). The surrogate's plan "
                         "arrays are abstract, so the cell lowers the jnp "
                         "fallback with IDENTICAL collectives — the "
                         "kernel census contract (docs/kernels.md)")
    ap.add_argument("--spmv-sstep", type=int, default=1,
                    help="communication-avoiding s-step filter cell for "
                         "--eigen (the '+s2'/'+s3' shape suffixes; "
                         "--spmv-sstep of repro.launch.solve): the "
                         "surrogate carries the real pattern's depth-s "
                         "ghost plan and the lowered filter runs "
                         "ceil(degree/s) exchanges — a single-width "
                         "seed plus width-doubled [w1|w2] group "
                         "payloads — instead of one per SpMV; with "
                         "--verify every exchange is attributed to the "
                         "chi(A^s) terms of sstep_collectives; plain "
                         "(non-overlap) cells only")
    ap.add_argument("--plan-mode", default="auto",
                    choices=["exact", "sampled", "auto"],
                    help="pattern-pass strategy for the --eigen cell's "
                         "planning (partition boundaries and the --plan "
                         "ranking): 'exact' (full scans, the partition "
                         "axis is dropped past the size gate), 'sampled' "
                         "(core/sketch.py: seeded row subsample, "
                         "Horvitz-Thompson chi/L estimates, coarsened "
                         "commvol descent), or 'auto' (exact below the "
                         "gate, sampled above; --plan-mode of "
                         "repro.launch.solve)")
    ap.add_argument("--plan", action="store_true",
                    help="with --eigen: print the χ-driven planner ranking "
                         "(core/planner.py) and the predicted vs HLO-measured "
                         "SpMV collective volume of the lowered cell (on a "
                         "planned partition also the before/after χ and "
                         "pad volumes)")
    ap.add_argument("--verify", action="store_true",
                    help="with --eigen: run the static communication "
                         "verifier on the compiled cell — attribute every "
                         "HLO collective to a χ-predicted term "
                         "(repro.analysis.census) and lint the lowered "
                         "neighbor schedule; exits nonzero on any "
                         "unattributed or missing collective")
    ap.add_argument("--fit-machine", action="store_true",
                    help="time real fused Chebyshev iterations of a small "
                         "instance across mesh splits on local devices, fit "
                         "b_c and kappa (MachineModel.fit), and save the "
                         "calibrated model to --fit-out for "
                         "`solve --machine <path>` planner rankings")
    ap.add_argument("--fit-out", default="machine_fit.json",
                    help="JSON path for the --fit-machine result")
    ap.add_argument("--machine", default="tpu-v5e",
                    help="machine model for the --plan ranking: 'tpu-v5e', "
                         "'meggie', or a JSON path saved by --fit-machine")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None, help="append JSON records here")
    args = ap.parse_args(argv)
    if args.spmv_schedule != "cyclic" and args.spmv_comm != "compressed":
        ap.error(f"--spmv-schedule {args.spmv_schedule} requires "
                 "--spmv-comm compressed")

    records = []
    try:
        if args.fit_machine:
            fit_machine(args.eigen, args.fit_out)
            return records
        if args.eigen:
            from ..core import perf_model as pm

            machine = pm.resolve_machine(args.machine)
            records.append(run_eigen(args.eigen, args.layout, args.multi_pod,
                                     plan=args.plan,
                                     spmv_comm=args.spmv_comm,
                                     spmv_schedule=args.spmv_schedule,
                                     spmv_balance=args.spmv_balance,
                                     spmv_reorder=args.spmv_reorder,
                                     spmv_kernel=args.spmv_kernel,
                                     spmv_sstep=args.spmv_sstep,
                                     plan_mode=args.plan_mode,
                                     machine=machine, verify=args.verify))
        elif args.all:
            for arch, shape, cell in iter_cells():
                if cell is None:
                    records.append({"arch": arch, "shape": shape,
                                    "mesh": "2x16x16" if args.multi_pod else "16x16",
                                    "status": "skip"})
                    continue
                records.append(run_cell(arch, shape, args.multi_pod))
        else:
            cfg = get_config(args.arch)
            cell = applicable_shapes(cfg)[args.shape]
            if cell is None:
                records.append({"arch": args.arch, "shape": args.shape,
                                "status": "skip"})
                print(f"[dryrun] {args.arch} x {args.shape}: SKIP (see DESIGN.md)")
            else:
                records.append(run_cell(args.arch, args.shape, args.multi_pod))
    finally:
        if args.out and records:
            with open(args.out, "a") as f:
                for r in records:
                    f.write(json.dumps(r) + "\n")
    if args.verify and any(r.get("verify_errors") for r in records):
        sys.exit(1)
    return records


if __name__ == "__main__":
    main()
