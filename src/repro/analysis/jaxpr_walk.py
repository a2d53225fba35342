"""Closed-jaxpr traversal + replica-uniformity dataflow for the auditor.

The collective-safety passes (``parity``, ``budget``, ``hostcalls``) all
need the same primitives, which live here:

  * :func:`walk` — depth-first traversal of a jaxpr INCLUDING every
    sub-jaxpr reachable through equation params (``cond`` branches,
    ``scan``/``while`` bodies, ``pjit``/``remat2``/``custom_*`` calls,
    ``shard_map`` bodies), yielding ``(eqn, path)`` pairs where ``path``
    is a stable, human-readable position string — the path-qualified
    part of every auditor diagnostic.
  * :func:`walk_trips` — the same traversal, each eqn with how often it
    runs: the product of the lengths of the scans around it.
  * :func:`collective_signature` — the ORDERED sequence of
    :class:`CollectiveCall` records (primitive, named axes, operand
    shapes/dtypes, comm-relevant params) a jaxpr would issue. Two
    program fragments with equal signatures launch identical collective
    sequences — the SPMD deadlock-freedom currency.
  * :func:`uniform_env` — a forward dataflow pass computing, for every
    variable, the set of mesh axes across which its value is provably
    IDENTICAL on all ranks.  ``lax.switch`` on such a variable is safe
    for any collective over axes inside that set: every rank of the
    collective's group takes the same branch.  Sources of uniformity:
    literals/consts (uniform everywhere), ``axis_index`` (uniform
    everywhere EXCEPT its axis), collectives (their result is uniform
    over the reduced axes), shard_map inputs (uniform over every manual
    axis their ``in_names`` do NOT shard).  Everything else propagates
    the intersection of its operands — deterministic ops preserve
    uniformity.  The analysis is conservative: "not provably uniform"
    never means "safe".
"""
from __future__ import annotations

import dataclasses
from typing import Any, Iterator

from jax.extend import core as jex_core

__all__ = [
    "COLLECTIVE_PRIMS",
    "HOST_CALLBACK_PRIMS",
    "CollectiveCall",
    "as_jaxpr",
    "subjaxprs",
    "walk",
    "walk_trips",
    "collective_signature",
    "count_collectives",
    "uniform_env",
    "shard_map_contexts",
]

# Named-axis communication primitives (jax.lax.* parallel operators as
# they appear in jaxprs).  ``axis_index`` reads the mesh coordinate but
# moves no data — it is a uniformity SOURCE, not a collective.
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "ppermute", "pbroadcast", "all_gather",
    "all_to_all", "psum_scatter", "pgather", "reduce_scatter",
})

# Host round-trips that must never appear inside a compiled train step
# (each one is a device->host sync under jit).
HOST_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "python_callback",
    "callback", "host_callback", "outside_call", "debug_print",
})


@dataclasses.dataclass(frozen=True)
class CollectiveCall:
    """One collective launch: everything that must match across ranks."""

    primitive: str
    axes: tuple[str, ...]              # named mesh axes (sorted)
    operands: tuple[tuple[tuple[int, ...], str], ...]   # ((shape, dtype), ...)
    params: tuple[tuple[str, str], ...] = ()            # perm / groups / ...
    path: str = ""                     # jaxpr position (diagnostics only)

    def matches(self, other: "CollectiveCall") -> bool:
        """Signature equality — everything except the jaxpr position."""
        return (self.primitive == other.primitive and self.axes == other.axes
                and self.operands == other.operands
                and self.params == other.params)

    def describe(self) -> str:
        ops = ", ".join(f"{dt}{list(shp)}" for shp, dt in self.operands)
        return f"{self.primitive}[{','.join(self.axes)}]({ops})"


def as_jaxpr(obj: Any) -> jex_core.Jaxpr:
    """Accept a Jaxpr, ClosedJaxpr, or anything with a ``.jaxpr`` chain."""
    seen = set()
    while not isinstance(obj, jex_core.Jaxpr):
        if id(obj) in seen or not hasattr(obj, "jaxpr"):
            raise TypeError(f"not a jaxpr: {type(obj).__name__}")
        seen.add(id(obj))
        obj = obj.jaxpr
    return obj


def eqn_axes(eqn) -> tuple[str, ...]:
    """Named mesh axes a primitive communicates over (sorted, str only)."""
    axes: list[str] = []
    for key in ("axes", "axis_name", "axis_index_groups_axes"):
        v = eqn.params.get(key)
        if v is None:
            continue
        for a in v if isinstance(v, (tuple, list)) else (v,):
            if isinstance(a, str):
                axes.append(a)
    return tuple(sorted(set(axes)))


def subjaxprs(eqn) -> list[tuple[str, jex_core.Jaxpr]]:
    """(label, sub-jaxpr) for every jaxpr stored in an equation's params.

    ``cond`` branches get ``branch=i`` labels (the parity checker keys on
    them); everything else is labelled by its param name.  The scan is
    generic — any future primitive carrying jaxprs in params is walked.
    """
    out: list[tuple[str, jex_core.Jaxpr]] = []
    for key, val in eqn.params.items():
        items = val if isinstance(val, (tuple, list)) else (val,)
        multi = isinstance(val, (tuple, list))
        for i, item in enumerate(items):
            if isinstance(item, jex_core.ClosedJaxpr):
                item = item.jaxpr
            if not isinstance(item, jex_core.Jaxpr):
                continue
            if eqn.primitive.name == "cond" and key == "branches":
                out.append((f"branch={i}", item))
            else:
                out.append((f"{key}[{i}]" if multi else key, item))
    return out


def walk(jaxpr: Any, path: str = "") -> Iterator[tuple[Any, str]]:
    """Depth-first (eqn, path) over a jaxpr and all nested sub-jaxprs."""
    j = as_jaxpr(jaxpr)
    for n, eqn in enumerate(j.eqns):
        here = f"{path}/{eqn.primitive.name}#{n}"
        yield eqn, here
        for label, sub in subjaxprs(eqn):
            yield from walk(sub, f"{here}.{label}")


def walk_trips(jaxpr: Any, trips: int = 1) -> Iterator[tuple[Any, int]]:
    """(eqn, trips) over a jaxpr and all nested sub-jaxprs, ``trips`` being
    how often the eqn runs per run of the jaxpr: the product of the lengths
    of the scans around it (a ``cond`` branch counts as taken, a ``while``
    body once)."""
    for eqn in as_jaxpr(jaxpr).eqns:
        yield eqn, trips
        inner = trips * (eqn.params["length"] if eqn.primitive.name == "scan"
                         else 1)
        for _, sub in subjaxprs(eqn):
            yield from walk_trips(sub, inner)


def _comm_params(eqn) -> tuple[tuple[str, str], ...]:
    """Comm-relevant non-axis params (permutation, explicit groups)."""
    out = []
    for key in ("perm", "axis_index_groups", "split_axis", "concat_axis",
                "all_gather_dimension", "tiled"):
        if eqn.params.get(key) is not None:
            out.append((key, repr(eqn.params[key])))
    return tuple(out)


def collective_signature(jaxpr: Any, path: str = "",
                         prims: frozenset[str] = COLLECTIVE_PRIMS,
                         ) -> tuple[CollectiveCall, ...]:
    """Ordered collective sequence of a jaxpr, nested control flow included.

    Note on loops: a ``scan``/``while`` body is included ONCE — the
    signature is the per-iteration sequence.  Branch parity of a switch
    nested in a loop still holds iff the per-iteration signatures match,
    so this is exactly what the parity checker needs (trip counts are
    rank-invariant under SPMD).
    """
    sig: list[CollectiveCall] = []
    for eqn, here in walk(jaxpr, path):
        if eqn.primitive.name not in prims:
            continue
        operands = tuple(
            (tuple(v.aval.shape), str(v.aval.dtype))
            for v in eqn.invars if hasattr(v, "aval"))
        sig.append(CollectiveCall(
            primitive=eqn.primitive.name, axes=eqn_axes(eqn),
            operands=operands, params=_comm_params(eqn), path=here))
    return tuple(sig)


def count_collectives(jaxpr: Any, primitive: str | None = None) -> int:
    """Number of collective eqns traced anywhere in a (closed) jaxpr.

    ``primitive="psum"`` counts just that primitive — the reusable form
    of the ad-hoc ``str(jaxpr).count("psum")`` spy the pipeline tests
    used to hand-roll (string counting also matched e.g. variable names;
    this counts equations).
    """
    want = frozenset({primitive}) if primitive else COLLECTIVE_PRIMS
    return sum(1 for eqn, _ in walk(jaxpr) if eqn.primitive.name in want)


# ------------------------------------------------------------- uniformity
def _inner_axis_index_axes(eqn) -> set[str]:
    """Axes any nested axis_index reads — conservative de-uniformizer."""
    axes: set[str] = set()
    for _, sub in subjaxprs(eqn):
        for inner, _ in walk(sub):
            if inner.primitive.name == "axis_index":
                a = inner.params.get("axis_name")
                for x in a if isinstance(a, (tuple, list)) else (a,):
                    if isinstance(x, str):
                        axes.add(x)
    return axes


def uniform_env(jaxpr: Any, in_uniform: list[frozenset[str]],
                all_axes: frozenset[str]) -> dict:
    """Forward pass: var -> axes over which its value is rank-uniform.

    ``in_uniform`` parallels the jaxpr's invars; constvars are treated as
    uniform over ``all_axes`` (closed-over constants are replicated).
    ``pjit``/``remat2``-style inline calls recurse with their operands'
    sets; opaque control flow (scan/while/cond) falls back to the
    intersection of its inputs minus any axis an inner ``axis_index``
    reads — sound, never more uniform than reality.
    """
    j = as_jaxpr(jaxpr)
    env: dict = {}
    for v, u in zip(j.invars, in_uniform):
        env[v] = frozenset(u)
    for v in j.constvars:
        env[v] = all_axes

    def read(x) -> frozenset[str]:
        if isinstance(x, jex_core.Literal):
            return all_axes
        return env.get(x, frozenset())

    for eqn in j.eqns:
        name = eqn.primitive.name
        ins = [read(x) for x in eqn.invars]
        base = frozenset(all_axes)
        for u in ins:
            base &= u
        if name == "axis_index":
            a = eqn.params.get("axis_name")
            drop = {x for x in (a if isinstance(a, (tuple, list)) else (a,))
                    if isinstance(x, str)}
            out = all_axes - drop
        elif name in COLLECTIVE_PRIMS and name not in ("ppermute", "pgather",
                                                       "all_to_all"):
            # reductions/gathers produce the same value on every member
            # rank; a ppermute/all_to_all result still varies per rank
            out = base | frozenset(eqn_axes(eqn))
        elif name in ("pjit", "closed_call", "core_call", "remat2",
                      "custom_jvp_call", "custom_vjp_call"):
            subs = subjaxprs(eqn)
            if len(subs) == 1:
                sub = subs[0][1]
                if len(sub.invars) == len(ins):
                    sub_env = uniform_env(sub, ins, all_axes)
                    outs = [sub_env.get(v, frozenset())
                            if not isinstance(v, jex_core.Literal)
                            else all_axes
                            for v in sub.outvars]
                    for ov, u in zip(eqn.outvars, outs):
                        env[ov] = u
                    continue
            out = base - _inner_axis_index_axes(eqn)
        elif subjaxprs(eqn):
            out = base - _inner_axis_index_axes(eqn)
        else:
            out = base
        for ov in eqn.outvars:
            env[ov] = out
    return env


def shard_map_contexts(jaxpr: Any) -> list[tuple[Any, str, frozenset[str],
                                                 list[frozenset[str]]]]:
    """Every shard_map body with its manual axes and per-input uniformity.

    Returns ``(body_jaxpr, path, manual_axes, in_uniform)`` tuples: an
    input is uniform over each manual axis its ``in_names`` entry does
    not shard (replicated params -> uniform over all manual axes; the
    batch -> varying over the DP axes).  This is the precise entry point
    the parity checker seeds :func:`uniform_env` with.
    """
    out = []
    for eqn, path in walk(jaxpr):
        if eqn.primitive.name != "shard_map":
            continue
        mesh = eqn.params.get("mesh")
        auto = eqn.params.get("auto") or frozenset()
        names = [str(a) for a in getattr(mesh, "axis_names", ())]
        manual = frozenset(n for n in names if n not in auto)
        in_names = eqn.params.get("in_names") or ()
        body = subjaxprs(eqn)[0][1]
        in_uniform = []
        for spec in in_names:
            sharded: set[str] = set()
            for ax_list in dict(spec).values():
                sharded.update(a for a in ax_list if isinstance(a, str))
            in_uniform.append(manual - sharded)
        out.append((body, f"{path}.jaxpr", manual, in_uniform))
    return out
