"""Collective-safety pass — the SPMD-deadlock classifier (COLL codes).

Inside a ``shard_map`` program every device executes the same trace, so a
collective deadlocks exactly when control flow lets devices *reach
different collectives*: a ``lax.cond`` whose branches issue different
collective sequences under a predicate that can differ across shards, or
a ``lax.while_loop`` containing collectives whose continuation predicate
can differ (some devices exit, the rest block in the next gather).

The pass therefore needs a *shard-uniformity* analysis: a value is
**uniform** when every device provably holds the same value. Sources of
uniformity are literals/constants and the outputs of full-axis reducing
collectives (``psum``/``pmin``/``pmax``/``all_gather`` over every mesh
axis — replicated by construction); ``axis_index`` and the shard_map
operands are varying. Uniformity propagates through pure ops, through
``pjit`` bodies, through ``cond`` (uniform predicate + all-branch-uniform
outputs), and through ``while`` carriers by monotone fixpoint (a carrier
stays uniform only if its init AND its body image are uniform). This is
exactly how the shipping BSP program proves safe: the wire-selection
``all_fit`` vote is ``psum``-derived (COLL102), and the round loop's
``total > 0 & rnd < max_rounds`` predicate is uniform because ``total``
is the psum termination vote and ``rnd`` a uniformly-incremented carrier.

Checks emitted (codes in :mod:`.findings`):

* COLL101 info — unconditional collectives (inventory);
* COLL102 info — cond-guarded collectives under a proven-uniform
  predicate;
* COLL103 warning — unproven predicate, but identical ordered branch
  collective sequences (safe today, one edit from COLL201);
* COLL201 error — unproven predicate AND mismatched branch sequences;
* COLL202 error — collective inside a loop with an unproven continuation
  predicate (ragged-exit deadlock);
* COLL203 error — a loop carrier patched from this round's ``all_gather``
  payload is never read before being carried out (the conflict pass would
  be consuming a stale snapshot).
"""
from __future__ import annotations

from typing import Dict, List, Set, Tuple

from .findings import Finding
from .jaxpr_walk import Literal, site_of
from .spmd import (COLLECTIVE_PRIMS, REDUCING_PRIMS, REPLICATING_PRIMS,
                   collective_eqns,
                   collective_signature, cond_branches, find_shard_jaxprs,
                   is_full_axis, mesh_axis_names, sub_jaxpr, while_parts)

_MAX_FIXPOINT_ITERS = 64


def _fmt_sig(sig) -> str:
    prim, axes, ins, _ = sig
    shapes = ", ".join(f"{list(s)}:{d}" for s, d in ins)
    return f"{prim}[{','.join(axes)}]({shapes})"


class _UniformEnv:
    """var -> is-shard-uniform for one jaxpr scope (Literals are uniform)."""

    def __init__(self):
        self._u: Dict[object, bool] = {}

    def get(self, v) -> bool:
        return True if isinstance(v, Literal) else self._u.get(v, False)

    def set(self, v, uniform: bool) -> None:
        self._u[v] = bool(uniform)


def _propagate(jaxpr, in_uniform, mesh_axes, *, emit=None, env_out=None
               ) -> List[bool]:
    """Run the uniformity transfer over one jaxpr level, recursing into
    sub-jaxprs. ``in_uniform`` matches ``jaxpr.invars``; constvars are
    uniform (replicated host constants). Returns per-outvar uniformity.

    ``emit`` (a callback collecting findings) is only passed on the FINAL
    pass — while-loop fixpoint iterations re-run the transfer silently so
    findings are never duplicated. ``env_out`` optionally receives the
    scope's final env (the stale-snapshot check re-reads it)."""
    env = _UniformEnv()
    for v in jaxpr.constvars:
        env.set(v, True)
    for v, u in zip(jaxpr.invars, in_uniform):
        env.set(v, u)

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        ins = [env.get(v) for v in eqn.invars]

        if prim == "axis_index":
            outs = [False] * len(eqn.outvars)
        elif prim in COLLECTIVE_PRIMS:
            replicated = (prim in REPLICATING_PRIMS
                          and is_full_axis(eqn, mesh_axes))
            outs = [replicated] * len(eqn.outvars)
            if emit is not None:
                emit("collective", eqn, None)
        elif prim == "cond":
            outs = _do_cond(eqn, ins, mesh_axes, emit)
        elif prim == "while":
            outs = _do_while(eqn, ins, mesh_axes, emit)
        elif prim in ("pjit", "closed_call", "core_call", "custom_jvp_call",
                      "custom_vjp_call", "remat", "checkpoint"):
            sub = sub_jaxpr(eqn.params.get("jaxpr",
                                           eqn.params.get("call_jaxpr")))
            if sub is None:
                outs = [all(ins)] * len(eqn.outvars)
            else:
                outs = _propagate(sub, ins, mesh_axes, emit=emit)
        elif prim == "scan":
            # conservative: recurse for findings with all-varying carries,
            # mark outputs varying (no scan in the shipping mesh program)
            sub = sub_jaxpr(eqn.params.get("jaxpr"))
            if sub is not None:
                _propagate(sub, [False] * len(sub.invars), mesh_axes,
                           emit=emit)
            outs = [False] * len(eqn.outvars)
        elif prim == "pallas_call":
            # device kernel: no collectives inside; uniform iff inputs are
            outs = [all(ins)] * len(eqn.outvars)
        else:
            # pure op (pvary included: it only re-tags the named-axis type)
            outs = [all(ins)] * len(eqn.outvars)

        for v, u in zip(eqn.outvars, outs):
            env.set(v, u)

    if env_out is not None:
        env_out.append(env)
    return [env.get(v) for v in jaxpr.outvars]


def _do_cond(eqn, in_uniform, mesh_axes, emit) -> List[bool]:
    branches = cond_branches(eqn)
    pred_uniform = in_uniform[0]
    operand_u = in_uniform[1:]
    # branch collectives are accounted by the cond-level COLL102/103/201
    # finding below, not the COLL101 unconditional inventory
    sub_emit = None if emit is None else (
        lambda kind, e, f: emit(kind, e, f) if kind == "finding" else None)
    branch_outs = [_propagate(b, list(operand_u), mesh_axes, emit=sub_emit)
                   for b in branches]
    sequences = [tuple(collective_signature(c) for c in collective_eqns(b))
                 for b in branches]
    has_colls = any(sequences)
    if has_colls and emit is not None:
        site = site_of(eqn)
        n = sum(len(s) for s in sequences)
        if pred_uniform:
            emit("finding", eqn, Finding(
                "COLL102", site,
                f"{n} collective(s) across {len(branches)} branch(es) under "
                f"a provably shard-uniform predicate — every device takes "
                f"the same branch"))
        elif all(s == sequences[0] for s in sequences[1:]):
            emit("finding", eqn, Finding(
                "COLL103", site,
                f"predicate not provably shard-uniform; the "
                f"{len(branches)} branches issue identical collective "
                f"sequences ({', '.join(_fmt_sig(s) for s in sequences[0])})"
                " — safe only while they stay identical"))
        else:
            rendered = " vs ".join(
                "[" + ", ".join(_fmt_sig(s) for s in seq) + "]"
                for seq in sequences)
            emit("finding", eqn, Finding(
                "COLL201", site,
                f"branch collective sequences mismatch under a predicate "
                f"not provably shard-uniform: {rendered}"))
    if not branch_outs:
        return [False] * len(eqn.outvars)
    if not pred_uniform:
        return [False] * len(eqn.outvars)
    return [all(bo[i] for bo in branch_outs)
            for i in range(len(eqn.outvars))]


def _do_while(eqn, in_uniform, mesh_axes, emit) -> List[bool]:
    cond_jaxpr, body_jaxpr, cn, bn = while_parts(eqn)
    cond_consts_u = in_uniform[:cn]
    body_consts_u = in_uniform[cn:cn + bn]
    carry_u = list(in_uniform[cn + bn:])

    # monotone fixpoint on carrier uniformity (silent iterations)
    for _ in range(_MAX_FIXPOINT_ITERS):
        out_u = _propagate(body_jaxpr, body_consts_u + carry_u, mesh_axes)
        new_u = [a and b for a, b in zip(carry_u, out_u)]
        if new_u == carry_u:
            break
        carry_u = new_u

    has_colls = bool(collective_eqns(body_jaxpr)) or \
        bool(collective_eqns(cond_jaxpr))
    if has_colls:
        pred_u = _propagate(cond_jaxpr, cond_consts_u + carry_u, mesh_axes)
        if emit is not None and not all(pred_u):
            emit("finding", eqn, Finding(
                "COLL202", site_of(eqn),
                "loop body issues collectives but the continuation "
                "predicate is not provably shard-uniform: devices can "
                "exit on different rounds (ragged-exit deadlock)"))

    if emit is not None:
        # final (finding-emitting) pass over the body with the stable env;
        # fixpoint iterations above ran silent so nothing duplicates
        _propagate(body_jaxpr, body_consts_u + carry_u, mesh_axes, emit=emit)
        _check_stale_carrier(eqn, body_jaxpr, emit)
    return [u for u in carry_u]


# ---------------------------------------------------------------------------
# COLL203: exchange-patched carriers must be read in-round
# ---------------------------------------------------------------------------
def _gather_derived_outputs(jaxpr) -> Tuple[Set[object], List[bool]]:
    """Forward taint from ``all_gather`` outputs through everything
    (scatters included — a patched buffer still derives from the payload).
    Returns (tainted vars at this level, per-outvar taint)."""
    return _gather_derived_outputs_with_inputs(
        jaxpr, [False] * len(jaxpr.invars))


def _gather_derived_outputs_with_inputs(jaxpr, in_taint
                                        ) -> Tuple[Set[object], List[bool]]:
    tainted: Set[object] = {v for v, t in zip(jaxpr.invars, in_taint) if t}

    def is_t(v):
        return (not isinstance(v, Literal)) and v in tainted

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "all_gather":
            tainted.update(eqn.outvars)
            continue
        if prim in REDUCING_PRIMS:
            # a reducing collective CONSUMES the payload: its output is a
            # fresh replicated aggregate (the termination vote), not an
            # exchanged buffer that still needs an in-round reader
            continue
        if prim == "cond":
            outs = [False] * len(eqn.outvars)
            for b in cond_branches(eqn):
                _, bouts = _gather_derived_outputs_with_inputs(
                    b, [is_t(v) for v in eqn.invars[1:]])
                outs = [a or bb for a, bb in zip(outs, bouts)]
            for v, t in zip(eqn.outvars, outs):
                if t:
                    tainted.add(v)
            continue
        if prim in ("pjit", "closed_call"):
            sub = sub_jaxpr(eqn.params.get("jaxpr"))
            if sub is not None:
                _, bouts = _gather_derived_outputs_with_inputs(
                    sub, [is_t(v) for v in eqn.invars])
                for v, t in zip(eqn.outvars, bouts):
                    if t:
                        tainted.add(v)
                continue
        if prim == "while":
            # nested loops (the fixpoint sweeps) hold no gathers in the
            # shipping program; if one ever does, taint all its outputs
            _, wbody, _, _ = while_parts(eqn)
            if wbody is not None and collective_eqns(wbody):
                tainted.update(eqn.outvars)
                continue
        if any(is_t(v) for v in eqn.invars):
            tainted.update(eqn.outvars)
    return tainted, [is_t(v) for v in jaxpr.outvars]


def _check_stale_carrier(while_eqn, body_jaxpr, emit) -> None:
    """COLL203: every body outvar that (a) is an array of more than one
    element and (b) derives from this round's ``all_gather`` payload must
    also be *read* by some body equation — otherwise the freshly-exchanged
    view only becomes visible next round and every in-round consumer (the
    conflict pass) saw stale state."""
    tainted, out_taint = _gather_derived_outputs(body_jaxpr)
    if not tainted:
        return
    # users: var -> equations consuming it (one level; sub-jaxpr consumers
    # count through their enclosing eqn's invars)
    uses: Dict[object, int] = {}
    for eqn in body_jaxpr.eqns:
        for v in eqn.invars:
            if not isinstance(v, Literal):
                uses[v] = uses.get(v, 0) + 1
    for v, t in zip(body_jaxpr.outvars, out_taint):
        if not t or isinstance(v, Literal):
            continue
        try:
            import numpy as np
            elems = int(np.prod(v.aval.shape)) if v.aval.shape else 1
        except Exception:
            elems = 1
        if elems <= 1:
            continue  # psum votes / counters: not snapshot buffers
        if uses.get(v, 0) == 0:
            emit("finding", while_eqn, Finding(
                "COLL203", site_of(while_eqn),
                f"loop carrier {v.aval.shape}:{v.aval.dtype} is patched "
                "from this round's exchange but never read before being "
                "carried out — in-round consumers see last round's state"))


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------
def check_collectives(closed_jaxpr, *, context: str = "") -> List[Finding]:
    """Run the collective-safety pass over every shard_map program inside
    ``closed_jaxpr``. Programs without shard_map produce no findings."""
    findings: List[Finding] = []
    import dataclasses as _dc

    for shard_eqn, body in find_shard_jaxprs(closed_jaxpr):
        mesh_axes = mesh_axis_names(shard_eqn)
        pending: List[Finding] = []
        uncond_colls: List[object] = []

        def emit(kind, eqn, finding):
            if kind == "finding":
                pending.append(finding)
            elif kind == "collective":
                uncond_colls.append(eqn)

        # shard operands are per-device data: varying
        _propagate(body, [False] * len(body.invars), mesh_axes, emit=emit)

        # every collective reached during propagation that did NOT get
        # classified by a cond/while finding is structurally unconditional
        # within its scope — inventory them (deduped per site/signature)
        seen = set()
        for eqn in uncond_colls:
            sig = collective_signature(eqn)
            key = (site_of(eqn), sig)
            if key in seen:
                continue
            seen.add(key)
            pending.append(Finding(
                "COLL101", site_of(eqn),
                f"unconditional collective {_fmt_sig(sig)}"))
        findings.extend(_dc.replace(f, context=context) if context else f
                        for f in pending)
    return findings
