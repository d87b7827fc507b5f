"""Uniformity dataflow + collective well-formedness over driver jaxprs.

The analysis is one recursive abstract interpretation of the ClosedJaxpr
tree (reusing `tools.jaxtrace.walk`'s open/close and source-chain
helpers).  The abstract value of every variable is the set of mesh axes
along which it may be **shard-varying** — the lattice is the powerset of
bound axis names ordered by inclusion, join = union, bottom = frozenset()
(mesh-uniform).  See docs/collective_contracts.md for the full write-up.

Transfer rules:

- default: the output of an equation varies along the union of its
  operands' axes;
- seeding: `shard_map` `in_specs` mark operands varying along every axis
  their PartitionSpecs mention (that axis *splits* the array — each shard holds
  different rows); replicated operands stay uniform.  `axis_index` is the
  other variation source — its output IS the shard coordinate;
- laundering: `psum`/`pmax`/`pmin`/`all_gather` remove their named axes
  from the varying set (every member of the replica group holds the same
  reduction/gather result);
- loop carries reach their fixpoint by iterating the body transfer until
  the carry sets stop growing (monotone over a finite lattice, so this
  terminates); findings and fingerprint entries are emitted only on the
  final post-fixpoint pass;
- leaving a `shard_map` strips that mesh's axes (outputs are global
  arrays again); `cond` outputs additionally join the predicate's axes
  (control dependence).

Checks:

- **NONUNIFORM_STOP**: a `while`/`cond` predicate that dominates a
  collective must be uniform along every axis that collective's
  rendezvous spans.  `ppermute`/`pshuffle` lower to XLA CollectivePermute
  whose rendezvous spans the *whole mesh*, so they demand uniformity
  along every bound axis; `psum`/`pmax`/`pmin`/`all_gather`/
  `all_to_all`/`reduce_scatter` rendezvous per named-axis replica group,
  so they demand only their named axes.  This is the PR 9 deadlock class
  (an unreduced per-shard continue flag under a CollectivePermute),
  caught at trace time.
- **PPERMUTE_PERM**: a `ppermute` permutation must be injective (unique
  sources, unique targets) with every index in [0, axis_size).  Partial
  injections are legal and intentional — jax zero-fills unaddressed
  destinations, which the mesh warm hand-off relies on — so this is an
  injectivity check, not a full-bijection check.  The block-sparse
  halo shifts of `decentral._block_neighbor_sum_fn` are full
  bijections and pass trivially.
- **AXIS_UNBOUND**: every collective axis name must be bound by an
  enclosing `shard_map` mesh at the collective's depth.
- **COND_SCHEDULE**: all `cond` branches must issue the identical
  ordered collective sequence — a collective in one branch only is a
  guaranteed rendezvous mismatch whenever the predicate ever differs
  across the mesh.

The per-driver **fingerprint** is the ordered list of communication
collectives (op x axes x operand shapes, plus the literal permutation
for ppermute) in program order — the driver's communication schedule.
`meshcheck_contracts.json` commits it; the CLI drift gate makes schedule
changes deliberate.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, FrozenSet, List, Tuple

from tools.jaxtrace import walk
from tools.jaxtrace.contracts import Finding

try:
    from jax._src.core import Literal  # type: ignore
except Exception:  # pragma: no cover - jax always present in this repo
    Literal = ()  # type: ignore

EMPTY: FrozenSet[str] = frozenset()

# Communication collectives by value semantics.  jax lowers pmean to
# psum+div so it never appears as a primitive, but newer jax spellings
# (psum2/psum_invariant) are aliased in defensively.
REDUCING = frozenset({"psum", "psum2", "psum_invariant", "pmax", "pmin",
                      "pmean"})
GATHERING = frozenset({"all_gather", "pgather"})
PERMUTING = frozenset({"ppermute", "pshuffle"})
SCATTERING = frozenset({"all_to_all", "reduce_scatter"})
COMM = REDUCING | GATHERING | PERMUTING | SCATTERING

# Fixpoint iteration cap: the carry lattice has at most |axes| levels per
# position, so growth stops after a handful of passes; the cap only
# guards against a non-monotone bug in this file.
_FIXPOINT_CAP = 32

# (contract, match-substring) -> mandatory reason; same W0 semantics as
# tools/jaxtrace (reasonless or stale entries are errors).  Empty today:
# every driver proves uniform as written.
WAIVERS: Dict[Tuple[str, str], str] = {}


@dataclasses.dataclass(frozen=True)
class Demand:
    """One collective's claim on every dominating predicate: trip counts
    must be uniform along `axis`, else members of the rendezvous group
    execute different numbers of collectives and the mesh deadlocks."""
    axis: str
    op: str
    where: str


@dataclasses.dataclass(frozen=True)
class Scope:
    """Mesh context of the jaxpr being interpreted."""
    path: Tuple[str, ...] = ()
    axis_sizes: Tuple[Tuple[str, int], ...] = ()

    def child(self, prim: str, axis_sizes=None) -> "Scope":
        return Scope(self.path + (prim,),
                     self.axis_sizes if axis_sizes is None else axis_sizes)

    @property
    def axes(self) -> FrozenSet[str]:
        return frozenset(n for n, _ in self.axis_sizes)

    def size(self, name: str):
        for n, s in self.axis_sizes:
            if n == name:
                return s
        return None


def _spec_axes(spec) -> FrozenSet[str]:
    """Mesh axes a shard_map ``PartitionSpec`` splits its operand over
    (each entry is None, one axis name, or a tuple of names)."""
    out = set()
    for entry in spec or ():
        if isinstance(entry, str):
            out.add(entry)
        elif entry is not None:
            out.update(entry)
    return frozenset(out)


class DriverAnalysis:
    """One driver's uniformity analysis: findings, fingerprint, stats."""

    def __init__(self, name: str):
        self.name = name
        self.findings: List[Finding] = []
        self.fingerprint: List[str] = []
        self.n_while = 0
        self.n_cond = 0
        self.vars_varying = 0
        self.vars_uniform = 0

    def run(self, closed) -> "DriverAnalysis":
        root = walk._open(closed)
        self.eval_jaxpr(root, [EMPTY] * len(root.invars), Scope(), True)
        return self

    # ------------------------------------------------------------------
    def _loc(self, eqn, scope: Scope) -> str:
        src = walk.source_line(eqn)
        path = "/".join(scope.path) or "<root>"
        return f"{path}::{eqn.primitive.name}" + (f" @ {src}" if src else "")

    def eval_jaxpr(self, jaxpr, in_axes: List[FrozenSet[str]], scope: Scope,
                   emit: bool) -> Tuple[List[FrozenSet[str]], List[Demand]]:
        """Abstract-interpret one open jaxpr.  Returns the varying-axes
        sets of its outvars and the rendezvous demands of every
        collective (transitively) inside it."""
        env: Dict[int, FrozenSet[str]] = {}

        def write(v, ax: FrozenSet[str]):
            ax = ax & scope.axes  # a value cannot vary along an unbound axis
            env[id(v)] = ax
            if emit and scope.axis_sizes:
                if ax:
                    self.vars_varying += 1
                else:
                    self.vars_uniform += 1

        def read(a) -> FrozenSet[str]:
            if isinstance(a, Literal):
                return EMPTY
            return env.get(id(a), EMPTY)

        for v in getattr(jaxpr, "constvars", ()):
            write(v, EMPTY)
        for v, ax in zip(jaxpr.invars, in_axes):
            write(v, ax)

        demands: List[Demand] = []

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            in_ax = [read(a) for a in eqn.invars]
            joined = frozenset().union(*in_ax) if in_ax else EMPTY

            if prim == "jit":
                sub = walk._open(eqn.params["jaxpr"])
                out_ax, dem = self.eval_jaxpr(sub, list(in_ax),
                                              scope.child(prim), emit)
                demands += dem
                for v, ax in zip(eqn.outvars, out_ax):
                    write(v, ax)

            elif prim == "shard_map":
                mesh = eqn.params.get("mesh")
                sizes = walk.mesh_axis_sizes(mesh, scope.axis_sizes)
                mesh_axes = frozenset(
                    dict(sizes)) - frozenset(dict(scope.axis_sizes))
                sub = walk._open(eqn.params["jaxpr"])
                sub_in = []
                for spec, ax in zip(eqn.params["in_specs"], in_ax):
                    sub_in.append(ax | _spec_axes(spec))
                while len(sub_in) < len(sub.invars):  # defensive
                    sub_in.append(joined)
                out_ax, dem = self.eval_jaxpr(
                    sub, sub_in, scope.child(prim, axis_sizes=sizes), emit)
                demands += dem
                for v, ax in zip(eqn.outvars, out_ax):
                    write(v, ax - mesh_axes)  # outputs are global again

            elif prim == "scan":
                nc = eqn.params.get("num_consts", 0)
                nk = eqn.params.get("num_carry", 0)
                sub = walk._open(eqn.params["jaxpr"])
                consts, xs = in_ax[:nc], in_ax[nc + nk:]
                carry = list(in_ax[nc:nc + nk])
                for _ in range(_FIXPOINT_CAP):
                    out_ax, _ = self.eval_jaxpr(sub, consts + carry + xs,
                                                scope.child(prim), False)
                    new = [c | o for c, o in zip(carry, out_ax[:nk])]
                    if new == carry:
                        break
                    carry = new
                out_ax, dem = self.eval_jaxpr(sub, consts + carry + xs,
                                              scope.child(prim), emit)
                demands += dem
                # static trip count == uniform by construction: no
                # predicate to check
                final = ([c | o for c, o in zip(carry, out_ax[:nk])]
                         + list(out_ax[nk:]))
                for v, ax in zip(eqn.outvars, final):
                    write(v, ax)

            elif prim == "while":
                self.n_while += 1
                cn = eqn.params.get("cond_nconsts", 0)
                bn = eqn.params.get("body_nconsts", 0)
                cond_j = walk._open(eqn.params["cond_jaxpr"])
                body_j = walk._open(eqn.params["body_jaxpr"])
                cond_consts = in_ax[:cn]
                body_consts = in_ax[cn:cn + bn]
                carry = list(in_ax[cn + bn:])
                for _ in range(_FIXPOINT_CAP):
                    out_ax, _ = self.eval_jaxpr(body_j, body_consts + carry,
                                                scope.child(prim), False)
                    new = [c | o for c, o in zip(carry, out_ax)]
                    if new == carry:
                        break
                    carry = new
                out_ax, body_dem = self.eval_jaxpr(
                    body_j, body_consts + carry, scope.child(prim), emit)
                pred_ax_list, cond_dem = self.eval_jaxpr(
                    cond_j, cond_consts + carry, scope.child(prim), emit)
                pred_ax = pred_ax_list[0] if pred_ax_list else EMPTY
                dem = body_dem + cond_dem
                demands += dem
                if emit:
                    self._check_pred("while_loop", pred_ax, dem, eqn, scope)
                for v, ax in zip(eqn.outvars,
                                 [c | o for c, o in zip(carry, out_ax)]):
                    write(v, ax)

            elif prim == "cond":
                self.n_cond += 1
                pred_ax, op_ax = in_ax[0], in_ax[1:]
                outs, fps = [], []
                all_dem: List[Demand] = []
                for br in eqn.params.get("branches", ()):
                    sub = walk._open(br)
                    saved, self.fingerprint = self.fingerprint, []
                    oax, dem = self.eval_jaxpr(sub, list(op_ax),
                                               scope.child(prim), emit)
                    fps.append(self.fingerprint)
                    self.fingerprint = saved
                    outs.append(oax)
                    all_dem += dem
                if emit and fps:
                    base = fps[0]
                    for bi, fp in enumerate(fps[1:], start=1):
                        if fp != base:
                            k = next((i for i, (x, z)
                                      in enumerate(zip(base, fp)) if x != z),
                                     min(len(base), len(fp)))
                            self.findings.append(Finding(
                                self.name, "COND_SCHEDULE",
                                f"cond branches 0 and {bi} issue different "
                                f"collective sequences ({len(base)} vs "
                                f"{len(fp)} ops, first divergence at op "
                                f"{k}); every branch must rendezvous "
                                "identically", self._loc(eqn, scope)))
                            break
                    self.fingerprint.extend(base)
                demands += all_dem
                if emit:
                    self._check_pred("cond", pred_ax, all_dem, eqn, scope)
                for i, v in enumerate(eqn.outvars):
                    ax = frozenset().union(*(o[i] for o in outs)) if outs \
                        else EMPTY
                    write(v, ax | pred_ax)  # control dependence

            elif prim == "pallas_call":
                # opaque on purpose: collectives are illegal inside
                # (jaxtrace PALLAS_COLLECTIVE); values pass through
                for v in eqn.outvars:
                    write(v, joined)

            elif prim in COMM or prim in ("axis_index", "pvary"):
                demands += self._collective(eqn, prim, joined, scope, emit,
                                            write)

            else:
                subs = [s for val in eqn.params.values()
                        for s in walk._subjaxprs(val)]
                if subs:
                    # unknown higher-order primitive (custom_jvp/vjp,
                    # remat, ...): conservative — every sub-input joins
                    # every eqn input, outputs join everything produced
                    agg = EMPTY
                    for s in subs:
                        so = walk._open(s)
                        oax, dem = self.eval_jaxpr(
                            so, [joined] * len(so.invars),
                            scope.child(prim), emit)
                        demands += dem
                        if oax:
                            agg |= frozenset().union(*oax)
                    for v in eqn.outvars:
                        write(v, joined | agg)
                else:
                    for v in eqn.outvars:
                        write(v, joined)

        return [read(v) for v in jaxpr.outvars], demands

    # ------------------------------------------------------------------
    def _collective(self, eqn, prim: str, joined: FrozenSet[str],
                    scope: Scope, emit: bool, write) -> List[Demand]:
        named = frozenset(walk.collective_axes(eqn))
        loc = self._loc(eqn, scope)

        if emit:
            for ax in sorted(named - scope.axes):
                self.findings.append(Finding(
                    self.name, "AXIS_UNBOUND",
                    f"collective `{prim}` names axis {ax!r} but only "
                    f"{sorted(scope.axes)} are bound at this mesh depth",
                    loc))

        if prim == "axis_index":
            for v in eqn.outvars:  # THE variation source
                write(v, named & scope.axes)
            return []
        if prim == "pvary":
            for v in eqn.outvars:
                write(v, joined | (named & scope.axes))
            return []

        if emit and prim in PERMUTING:
            self._check_perm(eqn, named, scope, loc)
        if emit:
            self.fingerprint.append(
                self._fingerprint_entry(eqn, prim, named, scope))

        if prim in PERMUTING:
            # XLA CollectivePermute rendezvous spans the WHOLE mesh
            demand_axes = scope.axes | named
        else:
            # per named-axis replica group
            demand_axes = named & scope.axes

        if prim in REDUCING or prim in GATHERING:
            out = joined - named        # laundered: group-uniform result
        else:
            out = joined | (named & scope.axes)
        for v in eqn.outvars:
            write(v, out)
        return [Demand(ax, prim, loc) for ax in sorted(demand_axes)]

    def _check_pred(self, kind: str, pred_ax: FrozenSet[str],
                    dem: List[Demand], eqn, scope: Scope):
        first: Dict[str, Demand] = {}
        for d in dem:
            if d.axis in pred_ax and d.axis not in first:
                first[d.axis] = d
        for ax in sorted(first):
            d = first[ax]
            self.findings.append(Finding(
                self.name, "NONUNIFORM_STOP",
                f"{kind} predicate is shard-varying along axis {ax!r} but "
                f"dominates collective `{d.op}` ({d.where}) whose "
                "rendezvous requires uniform trip counts along that axis; "
                f"reduce the predicate (e.g. pmax) over {ax!r} before "
                "branching", self._loc(eqn, scope)))

    def _check_perm(self, eqn, named: FrozenSet[str], scope: Scope,
                    loc: str):
        perm = tuple(eqn.params.get("perm", ()) or ())
        try:
            srcs = [int(s) for s, _ in perm]
            dsts = [int(d) for _, d in perm]
        except (TypeError, ValueError):
            return
        if len(set(srcs)) < len(srcs) or len(set(dsts)) < len(dsts):
            self.findings.append(Finding(
                self.name, "PPERMUTE_PERM",
                f"perm {[list(p) for p in perm]} is not injective on axis "
                f"{sorted(named)} (duplicate sources or targets); the "
                "permutation must be one-to-one on the axis", loc))
        for ax in sorted(named):
            size = scope.size(ax)
            if size is None:
                continue
            bad = sorted({i for i in srcs + dsts if not 0 <= i < size})
            if bad:
                self.findings.append(Finding(
                    self.name, "PPERMUTE_PERM",
                    f"perm index(es) {bad} out of range for axis {ax!r} "
                    f"of size {size}", loc))

    def _fingerprint_entry(self, eqn, prim: str, named: FrozenSet[str],
                           scope: Scope) -> str:
        shapes = []
        for v in eqn.invars:
            aval = getattr(v, "aval", None)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            dims = ",".join(str(d) for d in getattr(aval, "shape", ()))
            shapes.append(f"{aval.dtype}[{dims}]")
        path = "/".join(scope.path) or "<root>"
        entry = (f"{path}::{prim}[{','.join(sorted(named))}]"
                 f"({' '.join(shapes)})")
        if prim in PERMUTING:
            perm = [[int(s), int(d)]
                    for s, d in eqn.params.get("perm", ())]
            entry += f" perm={perm}"
        return entry


def analyze_driver(name: str, closed) -> DriverAnalysis:
    """Uniformity + well-formedness analysis of one traced driver."""
    return DriverAnalysis(name).run(closed)
