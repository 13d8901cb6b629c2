"""Static wire-cost model — bytes-on-wire accounting (WIRE codes).

The three-tier distributed exchange has a *closed-form* per-round byte
count, documented in DESIGN.md §Perf and deliberately **re-derived here
from the documented formulas** rather than imported from the runtime
modules — so the pass cross-checks two independent implementations and
code/doc drift in either becomes a lint error (WIRE201):

* **H-C4 boundary halo** — ``D * ceil(Bl / k) * 4`` bytes/round, with
  ``k = 32 // (bit_length(wire_colors) + 1)`` packed entries per int32
  word (``repro.parallel.compression``'s layout);
* **H-C1 full spill** — ``Vp * 2`` bytes/round (the packed-int16 gather);
* **H-C3 frontier slab** — ``D * cap_v * 4`` when ``(gid, color)`` packs
  into one word (``bit_length(Vp) + bit_length(wire_colors) <= 32``),
  else two int32 gathers totalling ``D * cap_v * 8``;
* **setup** — one ``D * Bl * 4`` boundary-map gather, outside the round
  loop (zero per-round id traffic).

:func:`check_wire_cost` walks the traced mesh program, attributes every
``all_gather`` to a tier by its structural position (pre-loop = setup;
in-loop true-branch of a gathering cond = slab; in-loop otherwise = the
configured round tier), and compares traced output bytes against the
closed forms. Scalar ``psum`` votes (<= 2 elements) are control plane,
inventoried in the cost table but never gated.

:func:`closed_form_table` / :func:`wire_cost_table` emit the
machine-readable cost table (also surfaced via
``python -m repro.analysis --distributed --json``); the ``dist_scale``
benchmark asserts its measured per-round bytes against it within the
plan-envelope padding tolerance.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from .findings import Finding
from .jaxpr_walk import site_of
from .spmd import (REDUCING_PRIMS, SpmdGeometry, aval_nbytes, cond_branches,
                   distributed_geometry, find_shard_jaxprs, iter_round_loops,
                   sub_jaxpr, while_parts)

# psum outputs at or below this element count are termination/fit votes
# (control plane), not wire payload
_VOTE_ELEMS = 2


# ---------------------------------------------------------------------------
# the closed forms (DESIGN.md §Perf — independent of the runtime modules)
# ---------------------------------------------------------------------------
def halo_round_bytes(num_devices: int, boundary_local: int,
                     wire_colors: int) -> int:
    """H-C4: ``D * ceil(Bl/k) * 4`` — bit-packed boundary halo words."""
    if boundary_local <= 0:
        return 0
    bits = max(1, int(wire_colors).bit_length()) + 1
    k = max(1, 32 // bits)
    return num_devices * (-(-boundary_local // k)) * 4


def spill_round_bytes(verts_global: int) -> int:
    """H-C1: the full packed-int16 ``[Vp]`` gather."""
    return verts_global * 2


def slab_round_bytes(num_devices: int, frontier_cap_v: int,
                     verts_global: int, wire_colors: int) -> int:
    """H-C3: ``(gid, color)`` slab entries — one packed int32 word when
    both fields fit, else two int32 gathers."""
    if frontier_cap_v <= 0:
        return 0
    packed = (wire_colors > 0 and
              int(verts_global).bit_length()
              + int(wire_colors).bit_length() <= 32)
    return num_devices * frontier_cap_v * (4 if packed else 8)


def setup_bytes(num_devices: int, boundary_local: int) -> int:
    """The one-time boundary->halo id-map gather (``D * Bl * 4``)."""
    return num_devices * boundary_local * 4 if boundary_local > 0 else 0


def closed_form_table(*, num_devices: int, verts_local: int,
                      boundary_local: int, wire_colors: int,
                      frontier_cap_v: int = 0, wire: str = "boundary",
                      scheme: str = "1d") -> Dict:
    """The machine-readable cost table for one program geometry — raw
    numbers, no tracing. The ``dist_scale`` benchmark evaluates this at
    the measured layout and asserts its accounting matches."""
    Vp = verts_local * num_devices
    tiers: Dict[str, Dict] = {}
    if wire == "boundary":
        tiers["halo"] = {
            "bytes_per_round": halo_round_bytes(num_devices, boundary_local,
                                                wire_colors),
            "formula": "D*ceil(Bl/k)*4, k=32//(bit_length(C)+1)"}
        tiers["setup"] = {
            "bytes_once": setup_bytes(num_devices, boundary_local),
            "formula": "D*Bl*4"}
    else:
        tiers["spill"] = {"bytes_per_round": spill_round_bytes(Vp),
                          "formula": "Vp*2"}
    if frontier_cap_v > 0:
        tiers["slab"] = {
            "bytes_per_round": slab_round_bytes(num_devices, frontier_cap_v,
                                                Vp, wire_colors),
            "formula": "D*cap_v*4 packed | D*cap_v*8 two-gather"}
    return {"wire": wire, "scheme": scheme, "num_devices": num_devices,
            "verts_local": verts_local, "verts_global": Vp,
            "boundary_local": boundary_local, "wire_colors": wire_colors,
            "frontier_cap_v": frontier_cap_v, "tiers": tiers}


def wire_cost_table(spec, statics) -> Optional[Dict]:
    """:func:`closed_form_table` for a plan spec/envelope (None for
    non-distributed strategies)."""
    from ..core.api import get_strategy
    if get_strategy(spec.strategy).wants != "host":
        return None
    g = distributed_geometry(spec, statics)
    return closed_form_table(
        num_devices=g.num_devices, verts_local=g.verts_local,
        boundary_local=g.boundary_cap, wire_colors=g.wire_colors,
        frontier_cap_v=g.frontier_cap_v, wire=g.wire,
        scheme=spec.partition)


# ---------------------------------------------------------------------------
# traced-program attribution
# ---------------------------------------------------------------------------
def _collect(jaxpr, sink, *, in_loop: bool, branch: Optional[int]):
    """Record every collective with its structural position. ``branch`` is
    the cond-branch index when inside an in-loop cond that gathers
    (1 = predicate-true = the slab wire), else None."""
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "all_gather" or prim in REDUCING_PRIMS:
            sink.append((eqn, in_loop, branch))
        elif prim == "while":
            _, body, _, _ = while_parts(eqn)
            if body is not None:
                _collect(body, sink, in_loop=True, branch=None)
        elif prim == "cond":
            for idx, b in enumerate(cond_branches(eqn)):
                _collect(b, sink, in_loop=in_loop,
                         branch=(idx if in_loop else branch))
        else:
            sub = sub_jaxpr(eqn.params.get("jaxpr",
                                           eqn.params.get("call_jaxpr")))
            if sub is not None:
                _collect(sub, sink, in_loop=in_loop, branch=branch)


def check_wire_cost(closed_jaxpr, geometry: SpmdGeometry, *,
                    context: str = "") -> List[Finding]:
    """Compare every traced collective's bytes against the closed-form
    tier accounting for ``geometry``. Returns WIRE findings (the WIRE101
    info carries the per-tier cost table entries)."""
    g = geometry
    findings: List[Finding] = []
    D, Vp = g.num_devices, g.verts_global
    exp_halo = halo_round_bytes(D, g.boundary_cap, g.wire_colors)
    exp_spill = spill_round_bytes(Vp)
    exp_slab = slab_round_bytes(D, g.frontier_cap_v, Vp, g.wire_colors)
    exp_setup = setup_bytes(D, g.boundary_cap)
    slab_packed = (g.wire_colors > 0 and
                   int(Vp).bit_length()
                   + int(g.wire_colors).bit_length() <= 32)

    for shard_eqn, body in find_shard_jaxprs(closed_jaxpr):
        colls: List = []
        _collect(body, colls, in_loop=False, branch=None)

        setup_sum = 0
        setup_sites = []
        slab_sum = 0
        slab_count = 0
        round_tier: List = []  # (eqn, bytes)
        votes = 0
        for eqn, in_loop, branch in colls:
            nbytes = sum(aval_nbytes(v) for v in eqn.outvars)
            if eqn.primitive.name != "all_gather":
                if all(_elems(v) <= _VOTE_ELEMS for v in eqn.outvars):
                    votes += 1
                    continue
                findings.append(Finding(
                    "WIRE202", site_of(eqn),
                    f"non-scalar {eqn.primitive.name} "
                    f"({nbytes} B) matches no documented wire tier",
                    context))
                continue
            if not in_loop:
                setup_sum += nbytes
                setup_sites.append(site_of(eqn))
                continue
            if branch == 1 and g.frontier_cap_v > 0:
                slab_sum += nbytes
                slab_count += 1
                continue
            round_tier.append((eqn, nbytes))

        # --- setup: the one-time boundary-map gather -----------------------
        if setup_sum != exp_setup:
            findings.append(Finding(
                "WIRE203", setup_sites[0] if setup_sites
                else site_of(shard_eqn, "plan:distributed"),
                f"pre-loop exchange ships {setup_sum} B, closed form says "
                f"D*Bl*4 = {exp_setup} B (D={D}, Bl={g.boundary_cap})",
                context))

        # --- slab tier -----------------------------------------------------
        if g.frontier_cap_v > 0:
            if slab_sum != exp_slab or \
                    slab_count != (1 if slab_packed else 2):
                findings.append(Finding(
                    "WIRE201", "core/distributed.py:slab_wire",
                    f"slab tier ships {slab_sum} B in {slab_count} "
                    f"gather(s), closed form says {exp_slab} B in "
                    f"{1 if slab_packed else 2} (D={D}, "
                    f"cap_v={g.frontier_cap_v}, packed={slab_packed})",
                    context))

        # --- the configured round tier ------------------------------------
        exp_round = exp_halo if g.wire == "boundary" else exp_spill
        tier_name = "halo" if g.wire == "boundary" else "spill"
        got_round = sum(b for _, b in round_tier)
        if len(round_tier) > 1:
            for eqn, b in round_tier[1:]:
                findings.append(Finding(
                    "WIRE202", site_of(eqn),
                    f"extra per-round all_gather ({b} B) beyond the single "
                    f"{tier_name}-tier exchange: unaccounted wire bytes",
                    context))
            got_round = round_tier[0][1]
        if got_round != exp_round or not round_tier:
            site = (site_of(round_tier[0][0]) if round_tier else
                    "core/distributed.py:"
                    + ("boundary_wire" if g.wire == "boundary"
                       else "full_wire"))
            findings.append(Finding(
                "WIRE201", site,
                f"{tier_name} tier ships {got_round} B/round, closed form "
                f"says {exp_round} B (D={D}, Bl={g.boundary_cap}, Vp={Vp}, "
                f"C={g.wire_colors})", context))

        table = closed_form_table(
            num_devices=D, verts_local=g.verts_local,
            boundary_local=g.boundary_cap, wire_colors=g.wire_colors,
            frontier_cap_v=g.frontier_cap_v, wire=g.wire)
        parts = [f"{name}={t.get('bytes_per_round', t.get('bytes_once'))}B"
                 for name, t in sorted(table["tiers"].items())]
        findings.append(Finding(
            "WIRE101", "core/distributed.py:_bsp_local",
            f"wire={g.wire} D={D} Vl={g.verts_local} Bl={g.boundary_cap} "
            f"C={g.wire_colors} cap_v={g.frontier_cap_v}: "
            + " ".join(parts) + f" votes/round<={votes}", context))
    return findings


def _elems(v) -> int:
    import numpy as np
    try:
        return int(np.prod(v.aval.shape)) if v.aval.shape else 1
    except Exception:
        return 1
