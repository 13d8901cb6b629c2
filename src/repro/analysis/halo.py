"""Halo exactness — interior-unreferencability as a static proof (HALO).

PR 9's boundary wire is exact because of a *hypothesis* the runtime tests
check pointwise: remote **interior** state is never referenced — every
cross-shard read lands on a boundary (or frontier-slab) vertex, and the
conflict pass reads the gathered payload only through the patched ``[Vp]``
snapshot view. This pass promotes that to a dataflow-reachability proof
over the traced mesh program, in two halves:

* **payload side** (HALO201) — every per-round ``all_gather`` inside the
  boundary-wire round loop must ship a *selection*: its operand element
  count must stay below the full local state width ``Vl`` (the packed
  halo words and the frontier slab both do; a mutation that routes the
  un-selected color vector onto the wire does not);
* **read side** (HALO202) — forward taint from the per-round gather
  outputs: the raw payload may flow into the carried snapshot/pending
  views only via scatters into ``[Vp]``-sized buffers (the sanctioned
  patch — including the index-normalization compares those scatters
  lower to). Any other path to an equality compare (the conflict
  predicate is ``color == color``) or to a scatter into a non-``[Vp]``
  buffer (the mex/forbid tables) would make raw remote state — interior
  entries included — referenceable, and is an error.

The proof is per-round: a value read from the *carried* snapshot is last
round's already-patched view, which is exactly the algorithm's contract
(DESIGN.md §Distributed), so carriers enter each round untainted.
HALO101 records the successful proof (gather count, payload widths).
"""
from __future__ import annotations

from typing import List, Optional, Set, Tuple

from .findings import Finding
from .jaxpr_walk import Literal, site_of
from .spmd import (REDUCING_PRIMS, SpmdGeometry, aval_elems, cond_branches,
                   find_shard_jaxprs, iter_round_loops, sub_jaxpr,
                   while_parts)

# the conflict predicate is an equality test on colors; lt/ge etc. appear
# in the (sanctioned) scatter index normalization, so only eq/ne are sinks
_COMPARE_SINKS = frozenset({"eq", "ne"})
_SCATTER_PRIMS = frozenset({
    "scatter", "scatter-add", "scatter-max", "scatter-min", "scatter-mul",
    "scatter-and", "scatter-or",
})


class _Taint:
    """Per-scope raw-payload taint with violation collection."""

    def __init__(self, Vp: int, context: str):
        self.Vp = Vp
        self.context = context
        self.violations: List[Finding] = []
        # (eqn, operand elems, cond-branch index or None): branch 1 of an
        # in-loop gathering cond is the slab wire — its payload is a
        # frontier selection whose capacity may legitimately reach Vl
        self.gathers: List[Tuple[object, int, Optional[int]]] = []


def _run(jaxpr, in_taint: List[bool], t: _Taint,
         branch: Optional[int] = None) -> List[bool]:
    tainted: Set[object] = {v for v, tt in zip(jaxpr.invars, in_taint) if tt}

    def is_t(v) -> bool:
        return (not isinstance(v, Literal)) and v in tainted

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "all_gather":
            t.gathers.append(
                (eqn, sum(aval_elems(v) for v in eqn.invars), branch))
            tainted.update(eqn.outvars)
            continue
        if prim in REDUCING_PRIMS:
            continue  # votes: reduced aggregates, not raw payload
        if prim in _SCATTER_PRIMS:
            operand, indices, updates = (eqn.invars + [None, None])[:3]
            data_tainted = is_t(indices) or is_t(updates)
            if data_tainted:
                op_elems = aval_elems(operand) if operand is not None else 0
                if op_elems == t.Vp:
                    # the sanctioned snapshot/pending patch: raw payload
                    # lands at gathered ids in the [Vp] view; downstream
                    # reads see the patched buffer, not the raw wire
                    continue
                t.violations.append(Finding(
                    "HALO202", site_of(eqn),
                    f"raw gathered payload written into a "
                    f"{tuple(operand.aval.shape)} buffer (not the [Vp]="
                    f"[{t.Vp}] snapshot view): remote state becomes "
                    f"referenceable outside the patch", t.context))
                tainted.update(eqn.outvars)
                continue
            if is_t(operand):
                tainted.update(eqn.outvars)
            continue
        if prim in _COMPARE_SINKS:
            if any(is_t(v) for v in eqn.invars):
                t.violations.append(Finding(
                    "HALO202", site_of(eqn),
                    "raw gathered payload reaches an equality compare (the "
                    "conflict-predicate class) without passing the [Vp] "
                    "snapshot patch", t.context))
                tainted.update(eqn.outvars)
            continue
        if prim == "cond":
            outs = [False] * len(eqn.outvars)
            for idx, b in enumerate(cond_branches(eqn)):
                bouts = _run(b, [is_t(v) for v in eqn.invars[1:]], t,
                             branch=idx)
                outs = [a or bb for a, bb in zip(outs, bouts)]
            for v, tt in zip(eqn.outvars, outs):
                if tt:
                    tainted.add(v)
            continue
        if prim == "while":
            # nested fixpoint sweeps: carriers enter untainted only if the
            # init values are untainted; conservative — taint everything
            # the loop touches when any input is tainted
            _, body, cn, bn = while_parts(eqn)
            in_t = [is_t(v) for v in eqn.invars]
            if body is not None:
                bouts = _run(body, in_t[cn:], t, branch=branch)
                for v, tt in zip(eqn.outvars, bouts):
                    if tt:
                        tainted.add(v)
            continue
        sub = sub_jaxpr(eqn.params.get("jaxpr",
                                       eqn.params.get("call_jaxpr")))
        if sub is not None and prim != "pallas_call":
            bouts = _run(sub, [is_t(v) for v in eqn.invars], t,
                         branch=branch)
            for v, tt in zip(eqn.outvars, bouts):
                if tt:
                    tainted.add(v)
            continue
        if any(is_t(v) for v in eqn.invars):
            tainted.update(eqn.outvars)
    return [is_t(v) for v in jaxpr.outvars]


def check_halo_exactness(closed_jaxpr, geometry: SpmdGeometry, *,
                         context: str = "") -> List[Finding]:
    """The exactness proof, run over every round loop of every shard_map
    program in ``closed_jaxpr``. Only meaningful for the boundary wire
    (the full tier ships everything by design and is exempt)."""
    g = geometry
    if g.wire != "boundary":
        return []
    findings: List[Finding] = []
    Vl = g.verts_local
    for shard_eqn, body in find_shard_jaxprs(closed_jaxpr):
        for wl in iter_round_loops(body):
            _, wbody, _, _ = while_parts(wl)
            if wbody is None:
                continue
            t = _Taint(g.verts_global, context)
            # carriers enter each round untainted: the carried snapshot is
            # LAST round's patched view, legitimately readable everywhere
            _run(wbody, [False] * len(wbody.invars), t)
            if not t.gathers:
                continue
            wide: List[Finding] = []
            for eqn, op_elems, br in t.gathers:
                # the slab branch (1 = predicate-true of a gathering cond)
                # ships frontier selections bounded by cap_v, which may
                # legitimately reach Vl on tiny envelopes
                limit = max(Vl, g.frontier_cap_v + 1) if br == 1 else Vl
                if op_elems >= limit:
                    wide.append(Finding(
                        "HALO201", site_of(eqn),
                        f"per-round payload ships {op_elems} entries >= "
                        f"{limit} (Vl={Vl}): the full local state "
                        "(interior entries included) crosses the wire — "
                        "the boundary selection was bypassed", context))
            findings.extend(wide)
            findings.extend(t.violations)
            if not t.violations and not wide:
                widths = ",".join(str(op) for _, op, _ in t.gathers)
                findings.append(Finding(
                    "HALO101", site_of(wl, "core/distributed.py:_bsp_local"),
                    f"exactness proven: {len(t.gathers)} per-round "
                    f"gather(s) (operand widths {widths}, all boundary/"
                    f"slab selections below Vl={Vl}); raw payload reaches "
                    "no conflict compare or foreign table — every read "
                    "routes through the [Vp] snapshot patch", context))
    return findings
