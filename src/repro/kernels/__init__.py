"""Pallas TPU kernels for the coloring hot spots (+ jnp oracles).

firstfit    — bitmask first-fit over ELL neighbor-color slabs (Alg. 1 5-6)
conflict    — edge-parallel conflict detection (Alg. 2 line 13)
round_fused — detect→mex→assign in ONE slab read: the firstfit bitset and
              the Alg. 2 predicate fused per vertex tile (ROADMAP item 2);
              reaches drivers as ``engine="fused_pallas"``

The kernels reach the coloring drivers exclusively through the
:class:`~repro.core.engine.MexBackend` registry: ``EllPallasMexBackend``
(``engine="ell_pallas"``) binds :func:`firstfit` to a graph's ELL geometry
(``Graph.to_device(layout="ell")``, or device-side ``engine.edge_slots``
under the distributed driver) and scatters each round's ``SweepSpec``
contributions into the [V, D] slab the kernel consumes. Drivers never
hand-wire kernel closures; registering a different kernel is a new
``MexBackend`` subclass (DESIGN.md §Engine). On the CPU the kernels run in
Pallas interpret mode (``ops.resolve_interpret``, decided per call).
"""
from .firstfit import firstfit
from .conflict import conflict_mask
from .ref import firstfit_ref, conflict_mask_ref
from .ops import ell_mex, ell_gather_colors, resolve_interpret
from .round_fused import (round_fused, round_fused_ref, pack_entries,
                          tile_conflict_counts, COLOR_MASK, FORBID_BIT,
                          CONFLICT_BIT)

__all__ = [
    "firstfit", "conflict_mask", "firstfit_ref", "conflict_mask_ref",
    "ell_mex", "ell_gather_colors", "resolve_interpret", "round_fused",
    "round_fused_ref", "pack_entries", "tile_conflict_counts", "COLOR_MASK",
    "FORBID_BIT", "CONFLICT_BIT",
]
