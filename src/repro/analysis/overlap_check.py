"""Overlap dependency checker: prove the split-phase engine's halo
exchange is independent of the local contraction, from the jaxpr alone.

The split-phase (``overlap=True``) SpMV engines issue the halo collective
*before* the local contraction so XLA's async scheduler can hide the
exchange behind local work. That only helps if the dependence structure
permits it; this pass traverses the closed jaxpr of an engine closure
(tracing only — nothing is compiled or executed) and checks two
conditions:

* **(A) independent exchange** — no halo collective (``all_to_all`` /
  ``ppermute``) takes a transitive data dependence on any contraction
  output. A violation means the exchange cannot start until local
  compute finishes: the engine silently lost its overlap.
* **(B) hideable work** — at least one contraction has no transitive
  dependence on any collective, i.e. there *is* local work the exchange
  can hide behind. The plain engines fail exactly this condition (their
  single contraction consumes the received halo), which is the built-in
  sanity check that the pass is not vacuous.

Contractions are ``lax.scan`` / ``while`` / ``dot_general`` /
``pallas_call`` equations (the ELL contraction is a scan over slot
columns in the jnp engines and a ``pallas_call`` in the kernelized
ones). Sub-jaxprs of ``pjit`` / ``shard_map`` / custom-derivative
wrappers are traversed with per-variable precision; bodies of
sequential loops and kernels are traversed conservatively (every body
input inherits the loop's union dependence set), so a collective nested
*inside* a sequential contraction loop is reported as dependent.

The round-pipelined compressed engine needs a sharper statement than
(A)/(B): its halo contraction is split into per-round sub-blocks, and
the whole point is that round ``r``'s contraction must not wait for any
round ``> r``'s collective. :func:`check_round_pipeline` proves this as
a *prefix-chain* property of the jaxpr — see its docstring. The
unpipelined body fails the proof (its single halo contraction witnesses
only the full chain), which is the built-in non-vacuity control.
"""
from __future__ import annotations

import dataclasses

from jax.extend import core as jax_core
import jax

__all__ = ["OverlapReport", "PipelineReport", "check_split_phase",
           "check_round_pipeline", "HALO_PRIMITIVES",
           "COLLECTIVE_PRIMITIVES", "CONTRACTION_PRIMITIVES"]

HALO_PRIMITIVES = frozenset({"all_to_all", "ppermute"})
COLLECTIVE_PRIMITIVES = HALO_PRIMITIVES | {
    "psum", "all_gather", "reduce_scatter", "pmax", "pmin", "pgather"}
CONTRACTION_PRIMITIVES = frozenset({"scan", "while", "dot_general",
                                    "pallas_call"})

# containers traversed with exact per-variable dependence mapping
# (their invars line up 1:1 with the sub-jaxpr's invars)
_PRECISE_CONTAINERS = ("pjit", "shard_map", "closed_call", "core_call",
                       "remat", "checkpoint", "custom_jvp_call",
                       "custom_vjp_call", "custom_jvp_call_jaxpr")


@dataclasses.dataclass
class OverlapReport:
    """Result of one split-phase dependency check."""

    collectives: list  # (label, primitive, depends_on_contraction: bool)
    contractions: list  # (label, depends_on_collective: bool)
    errors: list

    @property
    def ok(self) -> bool:
        return not self.errors

    @property
    def independent_contractions(self) -> int:
        """Contractions with no collective ancestor — the local work the
        exchange can hide behind."""
        return sum(1 for _, dep in self.contractions if not dep)

    def describe(self) -> str:
        lines = [f"collectives: {len(self.collectives)}, contractions: "
                 f"{len(self.contractions)} "
                 f"({self.independent_contractions} independent)"]
        for label, prim, dep in self.collectives:
            lines.append(f"  {label}: {prim} "
                         f"{'DEPENDS ON CONTRACTION' if dep else 'independent'}")
        lines += [f"  ERROR: {e}" for e in self.errors]
        return "\n".join(lines)


def _sub_jaxprs(value):
    if isinstance(value, jax_core.ClosedJaxpr):
        return [value.jaxpr]
    if isinstance(value, jax_core.Jaxpr):
        return [value]
    if isinstance(value, (tuple, list)):
        return [j for v in value for j in _sub_jaxprs(v)]
    return []


class _Recorder:
    def __init__(self):
        self.counter = 0
        self.collectives = []  # (label, prim, frozenset deps)
        self.contractions = []  # (label, frozenset deps)

    def fresh(self, prim: str) -> str:
        self.counter += 1
        return f"{prim}#{self.counter}"


_EMPTY: frozenset = frozenset()


def _walk(jaxpr, in_deps, rec: _Recorder):
    """Propagate per-variable dependence sets through one jaxpr; each set
    holds ("contract"|"coll", label) tags of ancestor equations. Returns
    the outvars' sets."""
    env: dict = {}

    def read(atom):
        if isinstance(atom, jax_core.Literal):
            return _EMPTY
        return env.get(atom, _EMPTY)

    for v, d in zip(jaxpr.invars, in_deps):
        env[v] = d
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        ins = [read(a) for a in eqn.invars]
        union = frozenset().union(*ins) if ins else _EMPTY
        subs = [j for v in eqn.params.values() for j in _sub_jaxprs(v)]
        if (prim in _PRECISE_CONTAINERS and len(subs) == 1
                and len(subs[0].invars) == len(eqn.invars)):
            outs = _walk(subs[0], ins, rec)
            for v, d in zip(eqn.outvars, outs):
                env[v] = d
            continue
        node = union
        if prim in CONTRACTION_PRIMITIVES:
            label = rec.fresh(prim)
            rec.contractions.append((label, union))
            node = node | {("contract", label)}
        if prim in COLLECTIVE_PRIMITIVES:
            label = rec.fresh(prim)
            rec.collectives.append((label, prim, union))
            node = node | {("coll", label)}
        # conservative traversal of remaining sub-jaxprs (loop bodies,
        # branches): every body input inherits the node's dependence set
        # and everything found inside feeds back into the outputs
        for sj in subs:
            inner = _walk(sj, [node] * len(sj.invars), rec)
            for d in inner:
                node = node | d
        for v in eqn.outvars:
            env[v] = node
    return [read(v) for v in jaxpr.outvars]


def check_split_phase(fn, *args, halo_primitives=HALO_PRIMITIVES,
                      expect_halo: bool = True) -> OverlapReport:
    """Trace ``fn(*args)`` (ShapeDtypeStructs suffice) and prove the
    split-phase conditions (A) and (B) on its jaxpr.

    ``expect_halo=False`` skips condition (B) and the no-halo error — for
    zero-halo cells (pillar layout / single shard) where the engine
    legitimately emits no exchange.
    """
    closed = jax.make_jaxpr(fn)(*args)
    rec = _Recorder()
    _walk(closed.jaxpr, [_EMPTY] * len(closed.jaxpr.invars), rec)

    def has(deps, kind):
        return any(k == kind for k, _ in deps)

    collectives = []
    errors = []
    halo_seen = False
    for label, prim, deps in rec.collectives:
        if prim not in halo_primitives:
            continue
        halo_seen = True
        dep = has(deps, "contract")
        collectives.append((label, prim, dep))
        if dep:
            culprits = sorted(lbl for k, lbl in deps if k == "contract")
            errors.append(
                f"halo collective {label} ({prim}) depends on contraction "
                f"output(s) {culprits}: the exchange cannot start before "
                f"local compute — split-phase overlap is lost")
    contractions = [(label, has(deps, "coll"))
                    for label, deps in rec.contractions]
    if expect_halo:
        if not halo_seen:
            errors.append("no halo collective found in the jaxpr — nothing "
                          "to overlap (wrong closure, or a zero-halo cell "
                          "checked with expect_halo=True)")
        elif not any(not dep for _, dep in contractions):
            errors.append(
                "no contraction is independent of the collectives: there "
                "is no local work the halo exchange could hide behind "
                "(the plain engines fail exactly this)")
    return OverlapReport(collectives=collectives, contractions=contractions,
                        errors=errors)


@dataclasses.dataclass
class PipelineReport:
    """Result of one round-pipeline prefix-chain proof."""

    n_rounds: int
    prefix_lengths: list  # sorted prefix lengths witnessed by contractions
    contractions: list  # (label, prefix length | None when not a prefix)
    errors: list

    @property
    def ok(self) -> bool:
        return not self.errors

    def describe(self) -> str:
        lines = [f"rounds: {self.n_rounds}, contractions: "
                 f"{len(self.contractions)}, prefix lengths witnessed: "
                 f"{self.prefix_lengths}"]
        for label, k in self.contractions:
            lines.append(f"  {label}: "
                         f"{'NOT A PREFIX' if k is None else f'prefix {k}'}")
        lines += [f"  ERROR: {e}" for e in self.errors]
        return "\n".join(lines)


def check_round_pipeline(fn, *args,
                         halo_primitives=HALO_PRIMITIVES) -> PipelineReport:
    """Trace ``fn(*args)`` and prove the round-pipelined engine's
    split-phase structure as a prefix-chain property of its jaxpr.

    Let ``c_1 .. c_n`` be the halo collectives in program order (the
    ``ppermute`` rounds of the compressed schedule). The proof requires:

    * **(a) prefix dependence** — every contraction's halo-collective
      dependence set is a *prefix* ``{c_1 .. c_k}`` of the chain. This
      is exactly "round ``r``'s contraction depends on no later round's
      collective": a contraction that consumed ``c_3`` without ``c_2``
      would wait on a round it does not need, and one whose set skips an
      earlier round would be reading an incompletely assembled buffer.
    * **(b) endpoints witnessed** — some contraction has prefix length
      0 (the local block, contracted before any exchange lands) and some
      has length ``n`` (the final round's halo slice is contracted).
    * **(c) strict interleaving** — for ``n >= 2``, some contraction
      witnesses a prefix length strictly between 0 and ``n``. The
      *unpipelined* split-phase body satisfies (a) and (b) — its single
      halo contraction depends on the full chain — but fails (c), so it
      cannot masquerade as pipelined; that failure is the checker's
      non-vacuity control (``make_spmv(..., pipeline=False)``).

    Bodies of sequential loops and Pallas kernels are traversed
    conservatively, so contractions nested inside the recorded ones
    re-witness the same prefix lengths and cannot weaken the proof.
    """
    closed = jax.make_jaxpr(fn)(*args)
    rec = _Recorder()
    _walk(closed.jaxpr, [_EMPTY] * len(closed.jaxpr.invars), rec)
    halo = [label for label, prim, _ in rec.collectives
            if prim in halo_primitives]
    order = {lbl: i for i, lbl in enumerate(halo)}
    n = len(halo)
    errors = []
    contractions = []
    lengths: set = set()
    for label, deps in rec.contractions:
        hidx = sorted(order[lbl] for k, lbl in deps
                      if k == "coll" and lbl in order)
        if hidx != list(range(len(hidx))):
            contractions.append((label, None))
            errors.append(
                f"contraction {label} depends on halo collectives "
                f"{[halo[i] for i in hidx]} — not a prefix of the "
                f"program-order round chain {halo}: it waits on a later "
                f"round's collective without consuming every earlier one")
            continue
        contractions.append((label, len(hidx)))
        lengths.add(len(hidx))
    if 0 not in lengths:
        errors.append(
            "no contraction is independent of the halo rounds (prefix "
            "length 0 missing): no local block is contracted while the "
            "exchange is in flight")
    if n and n not in lengths:
        errors.append(
            f"no contraction consumes the full {n}-round chain (prefix "
            f"length {n} missing): the final round's halo slice is never "
            f"contracted")
    if n >= 2 and not any(0 < k < n for k in lengths):
        errors.append(
            f"no contraction witnesses a strict prefix of the {n}-round "
            f"chain (lengths seen: {sorted(lengths)}): every halo "
            f"contraction waits for the last round's collective — the "
            f"engine is not round-pipelined")
    return PipelineReport(n_rounds=n, prefix_lengths=sorted(lengths),
                          contractions=contractions, errors=errors)
