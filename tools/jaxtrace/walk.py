"""Recursive jaxpr walker with structural context.

`jax.make_jaxpr` gives the program the compiler actually traces — after
jnp sugar, broadcasting, weak-type promotion and vmap batching have all
been lowered — but as a *tree*: `scan`/`while`/`cond`/`jit`/`shard_map`/
`pallas_call` equations each carry whole sub-jaxprs in their params.
This module flattens that tree into a stream of `(eqn, ctx)` pairs where
`Ctx` records everything the contract checks need to know about *where*
an equation sits:

- `inside_pallas`: the walk crossed a `pallas_call` boundary (collectives
  are illegal there — declint R5's IR-level twin);
- `axis_names`: mesh axis names in scope, harvested from enclosing
  `shard_map` equations (collectives must resolve against them);
- `in_loop` / `loop_scale` / `dynamic_loops`: whether we are inside a
  loop body, the product of enclosing *static* scan lengths (for the
  cost model), and how many enclosing `while` loops have trace-unknown
  trip counts;
- `const_vars`: ids of variables known loop-invariant in the current
  jaxpr (scan/while const sections, closed-over consts, and jit
  pass-throughs of the same) — the cast-churn detector flags
  `convert_element_type` of these inside loop bodies, because that cast
  re-executes every ADMM round over an operand that never changes.

The recursion pattern is deliberately duck-typed (`hasattr(v, "eqns")`
for open jaxprs, `hasattr(v.jaxpr, "eqns")` for ClosedJaxpr) so new
higher-order primitives walk without code changes.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Iterator, Tuple

# Params that hold sub-jaxprs on the jax we pin (verified on 0.9):
#   scan   -> jaxpr (Closed), num_consts, num_carry, length
#   while  -> cond_jaxpr/body_jaxpr (Closed), cond_nconsts/body_nconsts
#   cond   -> branches (tuple of Closed)
#   jit    -> jaxpr (Closed)
#   shard_map -> jaxpr (open), mesh, in_specs
#   pallas_call -> jaxpr (open), grid, interpret
#   custom_jvp/vjp_call -> call_jaxpr (Closed)


@dataclasses.dataclass(frozen=True)
class Ctx:
    """Structural context of one equation inside the walked program."""
    path: Tuple[str, ...] = ()
    inside_pallas: bool = False
    axis_names: frozenset = frozenset()
    # (axis name, size) pairs for every mesh axis in scope, harvested from
    # enclosing shard_map meshes — tools/meshcheck validates ppermute
    # permutations against these sizes (a perm index >= the axis size is
    # the wrong-axis-confusion bug class).
    axis_sizes: Tuple[Tuple[str, int], ...] = ()
    in_loop: bool = False
    loop_scale: int = 1
    dynamic_loops: int = 0
    const_vars: frozenset = frozenset()  # ids of loop-invariant Vars

    def child(self, **kw) -> "Ctx":
        return dataclasses.replace(self, **kw)

    def axis_size(self, name: str):
        for n, s in self.axis_sizes:
            if n == name:
                return s
        return None


def _open(j):
    """Open jaxpr behind either an open Jaxpr or a ClosedJaxpr.

    ClosedJaxpr forwards `.eqns`, so probe for the wrapper's `.jaxpr`
    attribute first — the open Jaxpr is the one with `.invars`."""
    inner = getattr(j, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    return j


def _is_jaxpr_like(v) -> bool:
    return hasattr(v, "eqns") or (hasattr(v, "jaxpr")
                                  and hasattr(getattr(v, "jaxpr"), "eqns"))


def _subjaxprs(value) -> Iterator[Any]:
    """Jaxpr-like objects inside one param value (possibly tuple-nested)."""
    if _is_jaxpr_like(value):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _subjaxprs(v)


def _const_section(prim: str, key: str, eqn, sub) -> frozenset:
    """Var ids in `sub` that are loop-invariant: closed-over constvars
    always; const sections of scan/while; jit invars whose call-site
    operand was itself a known const (positional pass-through)."""
    ids = {id(v) for v in getattr(sub, "constvars", ())}
    invars = sub.invars
    if prim == "scan":
        ids |= {id(v) for v in invars[:eqn.params.get("num_consts", 0)]}
    elif prim == "while":
        n = (eqn.params.get("cond_nconsts", 0) if key == "cond_jaxpr"
             else eqn.params.get("body_nconsts", 0))
        ids |= {id(v) for v in invars[:n]}
    return frozenset(ids)


def _child_ctx(eqn, key: str, sub_open, ctx: Ctx) -> Ctx:
    prim = eqn.primitive.name
    kw: dict = {"path": ctx.path + (prim,)}
    if prim == "pallas_call":
        kw["inside_pallas"] = True
    if prim == "shard_map":
        mesh = eqn.params.get("mesh")
        names = tuple(getattr(mesh, "axis_names", ()) or ())
        kw["axis_names"] = ctx.axis_names | frozenset(
            n for n in names if isinstance(n, str))
        kw["axis_sizes"] = mesh_axis_sizes(mesh, ctx.axis_sizes)
    if prim == "scan":
        kw["in_loop"] = True
        kw["loop_scale"] = ctx.loop_scale * int(eqn.params.get("length", 1))
    if prim == "while":
        kw["in_loop"] = True
        kw["dynamic_loops"] = ctx.dynamic_loops + 1
    # propagate loop-invariance through the boundary, then add this
    # sub-jaxpr's own const sections
    carried = set()
    if prim == "jit" and len(eqn.invars) == len(sub_open.invars):
        from jax._src.core import Literal  # type: ignore
        for atom, v in zip(eqn.invars, sub_open.invars):
            if isinstance(atom, Literal) or id(atom) in ctx.const_vars:
                carried.add(id(v))
    kw["const_vars"] = (frozenset(carried)
                        | _const_section(prim, key, eqn, sub_open))
    return ctx.child(**kw)


def mesh_axis_sizes(mesh, outer: Tuple[Tuple[str, int], ...] = ()
                    ) -> Tuple[Tuple[str, int], ...]:
    """Merge a shard_map mesh's (axis, size) pairs over `outer` scope.

    `mesh.shape` is an ordered name->size mapping on the jax we pin;
    inner bindings shadow outer ones of the same name."""
    try:
        items = tuple((str(n), int(s))
                      for n, s in dict(getattr(mesh, "shape", {})).items())
    except Exception:
        items = ()
    inner = {n for n, _ in items}
    return tuple((n, s) for n, s in outer if n not in inner) + items


def collective_axes(eqn) -> Tuple[str, ...]:
    """Mesh axis names a collective equation is bound to, in param order.

    Reads both the `axes` param (psum/pmax/pmin, which may mix in
    positional int axes — filtered out) and the `axis_name` param
    (ppermute/all_gather/axis_index, scalar or tuple).  Shared between
    jaxtrace's AXIS_NAME contract and tools/meshcheck."""
    names = []
    for key in ("axes", "axis_name"):
        v = eqn.params.get(key)
        if v is None:
            continue
        for n in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(n, str):
                names.append(n)
    return tuple(names)


def iter_jaxprs(closed) -> Iterator[Tuple[Any, Ctx]]:
    """Yield every (open jaxpr, Ctx) in the tree, root first."""
    root = _open(closed)
    ctx = Ctx(const_vars=frozenset(id(v)
                                   for v in getattr(root, "constvars", ())))
    stack = [(root, ctx)]
    while stack:
        jaxpr, c = stack.pop()
        yield jaxpr, c
        for eqn in jaxpr.eqns:
            for key, val in eqn.params.items():
                for sub in _subjaxprs(val):
                    sub_open = _open(sub)
                    stack.append((sub_open, _child_ctx(eqn, key, sub_open, c)))


def iter_eqns(closed) -> Iterator[Tuple[Any, Ctx, Any]]:
    """Yield (eqn, ctx, enclosing open jaxpr) over the whole tree."""
    for jaxpr, ctx in iter_jaxprs(closed):
        for eqn in jaxpr.eqns:
            yield eqn, ctx, jaxpr


def source_line(eqn) -> str:
    """Best-effort `file:line (fn)` chain for an equation, innermost last,
    '' if unavailable.  Several frames are kept so findings inside shared
    helpers (e.g. a pad utility) still name the public wrapper that
    reached them — waivers key on those names."""
    try:
        from jax._src import source_info_util
        s = source_info_util.summarize(eqn.source_info, num_frames=4)
        return " <- ".join(reversed(s.splitlines())) if s else ""
    except Exception:
        return ""


def primitive_counts(closed) -> dict:
    counts: dict = {}
    for eqn, _, _ in iter_eqns(closed):
        name = eqn.primitive.name
        counts[name] = counts.get(name, 0) + 1
    return counts
