"""The pluggable speculative-coloring engine (DESIGN.md §2, §Engine).

The paper's central finding is that ONE scheme — speculate, then resolve —
spans radically different machines once two inner pieces are specialized per
architecture: the first-fit ("mex") inner loop and the conflict pass. The
seed hard-wired one mex formulation and re-implemented the speculative
fixpoint three times (iterative / dataflow / distributed). This module is
the extraction:

* :class:`MexBackend` — a named, registered first-fit engine. Four ship:

  - ``"sort"``       the segmented sort-based mex (O(E log E) per sweep,
                     :func:`repro.core.mex.segment_mex`) — works on any
                     edge-list layout, no color bound needed;
  - ``"bitmap"``     a dense per-vertex forbidden **bitmap** built with one
                     scatter-or over the edge list — O(E) per sweep plus an
                     O(V·C) free-bit scan (the Rokos-style cheap inner
                     loop, arXiv:1505.04086); needs a static color bound,
                     taken from the graph's max degree;
  - ``"ell_pallas"`` the Pallas TPU ``firstfit`` kernel over an ELL slab,
                     fed by an O(E) edge→(row, slot) scatter; needs the
                     graph built with ``to_device(layout="ell")`` (or a
                     device-side :func:`edge_slots` mapping);
  - ``"fused_pallas"`` the Pallas ``round_fused`` kernel: the same bitmask
                     mex fused with the Alg. 2 conflict predicate in one
                     slab read (ELL requirements as ``"ell_pallas"``).

* :class:`SweepSpec` — the per-round edge-space description every driver
  lowers its precedence semantics into: which edges forbid, and whether an
  edge's contribution tracks the live color vector (``dyn``) or is frozen
  for the round (``static_c`` — e.g. the distributed snapshot gather).

* :func:`fixpoint_sweep` — THE speculation inner loop: chaotic sweeps of
      c[v] <- mex{ contribution(e) : e forbids v }      (pending v only)
  until a fixpoint, shared by ITERATIVE's phase 1, DATAFLOW, and the
  distributed local solve. No algorithm module carries its own sweep loop.

Registering a new backend (a GPU segmented-scan, a multi-host variant, ...)
is ~20 lines: subclass :class:`MexBackend`, implement ``bind``, call
:func:`register_backend` — every driver then accepts it via ``engine=``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from .mex import segment_mex

# A bound mex engine: (key_v [M], key_c [M]) -> mex [V] int32 (>= 1).
# key_v[i] is the vertex the edge forbids (num_vertices = inert padding);
# key_c[i] the forbidden color (0 = no constraint).
MexFn = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]

# A slab-bound mex engine (the frontier path): (key_v [cap_e] slab rows,
# key_c [cap_e], slot [cap_e] within-row positions) -> mex [cap_v]. The
# extra ``slot`` operand carries the per-round ELL geometry that the
# full-graph bind closes over statically.
SlabMexFn = Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray]

_INT32_MAX = jnp.iinfo(jnp.int32).max

# The ``fused_pallas`` engine's ELL slab width where a plan's max degree is
# above it (a binned layout, :meth:`MexBackend.layout_width`). Rows of
# degree at most this keep the slab and the ``round_fused`` kernel, whose
# bitset is sized from it; longer rows (hubs) take the per-edge
# :func:`repro.core.mex.segment_mex` (``hub=True``), whose work grows with
# their edges. The slab's memory grows with it, the hub edges' share falls
# with it.
HUB_WIDTH = 256


class SweepSpec(NamedTuple):
    """Per-round, edge-space description of 'who forbids whom with what'.

    key_v:    [M] int32 in [0, V]; V marks an inert edge this round.
    dyn_idx:  [M] int32 in [0, V]; gather index into the live (padded)
              color vector for dynamic contributions.
    dyn:      [M] bool; True = contribution re-read from the live colors
              every sweep, False = frozen at ``static_c`` for the round.
    static_c: [M] int32; the frozen contribution (distributed snapshot
              colors; 0 where unused).
    """

    key_v: jnp.ndarray
    dyn_idx: jnp.ndarray
    dyn: jnp.ndarray
    static_c: jnp.ndarray


def num_color_words(max_colors: int) -> int:
    """uint32 words needed so colors [1, max_colors] AND the next free
    candidate all fit: 32*words >= max_colors + 2."""
    return max(1, -(-(int(max_colors) + 2) // 32))


def _resolve_words(words: Optional[int], max_colors: int, name: str) -> int:
    """Shared words-capacity resolution for table-based backends. A color
    bound is always required — an unbounded table can silently drop forbids
    and corrupt colorings, so a ``words=`` override adjusts capacity above
    the bound rather than substituting for it."""
    if max_colors <= 0:
        raise ValueError(
            f"{name} engine needs a static color bound: build the graph "
            "via Graph.to_device() (it carries max_degree)")
    from ..kernels.round_fused import COLOR_MASK  # deferred: core importable solo
    if max_colors > COLOR_MASK:
        # a color value at 2^28 IS round_fused's FORBID bit: a packed entry
        # carrying it would forbid nothing and conflict with everything, so
        # no table backend accepts a bound the packed layout cannot encode
        raise ValueError(
            f"{name} engine: max_colors={max_colors} exceeds the packed-"
            f"entry color field (bits 0..27, max {COLOR_MASK}); "
            "colors that large alias the FORBID/CONFLICT predicate bits")
    if words is not None:
        words = int(words)
        if words < num_color_words(max_colors):
            raise ValueError(
                f"{name} engine: words={words} gives {32 * words} color "
                f"slots, below the graph's Delta+2 bound of "
                f"{max_colors + 2}; use words >= {num_color_words(max_colors)}"
                " (or omit words to derive it)")
        return words
    return num_color_words(max_colors)


# --------------------------------------------------------------------------
# backends
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MexBackend:
    """Base class: a named first-fit engine, bound per graph/partition.

    ``bind`` receives everything static a backend may specialize on:
      num_vertices  segment count (local V under the distributed driver);
      max_colors    a static upper bound on any color value that can appear
                    (graph max degree + 1, possibly capped by a
                    caller-asserted color_bound; 0 = unknown);
      ell_slot      [M] int32 per-edge slot within its vertex row, or None
                    (layouts that need it: build via Graph.to_device(
                    layout="ell") or device-side edge_slots());
      ell_width     static ELL slab width (max row length);
      max_degree    the graph's true max degree, independent of any
                    color_bound cap (-1 = unknown) — what ELL completeness
                    is checked against.
    It returns the per-sweep ``MexFn``.
    """

    name = "abstract"
    needs_ell = False          # True: bind() requires ell_slot/ell_width
    needs_color_bound = False  # True: bind() requires max_colors > 0; a
                               # words= override only raises capacity above
                               # that bound, it cannot substitute for it

    def bind(self, *, num_vertices: int, max_colors: int = 0,
             ell_slot: Optional[jnp.ndarray] = None,
             ell_width: int = 0, max_degree: int = -1) -> MexFn:
        raise NotImplementedError

    def bind_slab(self, *, capacity: int, max_colors: int = 0,
                  ell_width: int = 0, max_degree: int = -1) -> SlabMexFn:
        """Bind the backend to a fixed-capacity frontier slab
        (repro.core.frontier): segments are the ``capacity`` slab rows, not
        the graph's vertices — the bitmap backend's table shrinks from
        (V+1, C) to (capacity+1, C), the sort backend's segment space to
        ``capacity``. The returned callable takes a per-round ``slot``
        operand (each edge's position within its slab row) so ELL-style
        backends can scatter a compacted slab without a static geometry.

        The default adapter covers layout-free backends; ``needs_ell``
        backends override it."""
        if self.needs_ell:  # pragma: no cover - every needs_ell backend
            raise NotImplementedError(  # must provide its own slab bind
                f"mex backend {self.name!r} needs an ELL slab bind override")
        mex = self.bind(num_vertices=capacity, max_colors=max_colors,
                        max_degree=max_degree)
        return lambda key_v, key_c, slot: mex(key_v, key_c)

    def layout_width(self, max_degree: int) -> int:
        """The ELL width a plan lays its graphs out at for this backend,
        from the envelope's max degree: the full width. A narrower width
        makes a binned layout (:meth:`repro.core.graph.Graph.host_layout`),
        which only an engine with a hub path accepts."""
        return max(1, int(max_degree))

    def resolved(self) -> "MexBackend":
        """This backend with its call-time defaults decided — a Pallas
        backend's ``interpret=None`` becomes the current backend's mode
        (:func:`repro.kernels.ops.resolve_interpret`). :func:`get_backend`
        returns resolved instances, so the backend a jit sees as a static
        argument never carries an undecided ``None``."""
        if getattr(self, "interpret", False) is None:
            from ..kernels.ops import resolve_interpret
            return dataclasses.replace(self, interpret=resolve_interpret())
        return self


@dataclasses.dataclass(frozen=True)
class SortMexBackend(MexBackend):
    """Today's segmented-sort mex: O(E log E) per sweep, layout-free, no
    color bound required — the TPU-friendly default."""

    name = "sort"

    def bind(self, *, num_vertices: int, max_colors: int = 0,
             ell_slot=None, ell_width: int = 0, max_degree: int = -1) -> MexFn:
        V = num_vertices
        # synthetic (v, 0) pairs guarantee every segment is populated
        syn_v = jnp.arange(V, dtype=jnp.int32)
        syn_c = jnp.zeros((V,), jnp.int32)

        def mex(key_v, key_c):
            return segment_mex(
                jnp.concatenate([key_v, syn_v]),
                jnp.concatenate([key_c, syn_c]), V)

        return mex


@dataclasses.dataclass(frozen=True)
class BitmapMexBackend(MexBackend):
    """Dense forbidden-bitmap mex: one O(E) scatter-or over the edge list
    into a per-vertex forbidden table of C = 32*``words`` color slots, then
    an O(V*C) free-slot scan — no sort, the Rokos-style cheap inner loop.

    XLA has no bitwise-or scatter primitive, so the table holds one byte
    per color slot (the unpacked view of the Rokos uint32-word bitmap);
    duplicate forbids make the ``set`` idempotent, which is exactly the
    "or". ``words`` overrides the capacity derived from the graph's max
    degree (Delta+2 colors always suffice for greedy, so the derived bound
    is exact, never heuristic).
    """

    name = "bitmap"
    needs_color_bound = True
    words: Optional[int] = None

    def bind(self, *, num_vertices: int, max_colors: int = 0,
             ell_slot=None, ell_width: int = 0, max_degree: int = -1) -> MexFn:
        V = num_vertices
        words = _resolve_words(self.words, max_colors, self.name)
        C = 32 * words
        value = lax.broadcasted_iota(jnp.int32, (1, C), 1)

        def mex(key_v, key_c):
            # scatter-or: colors >= C land out of range and drop — they can
            # never lower a mex that (by the Delta+2 bound) stays < C
            forb = (jnp.zeros((V + 1, C), jnp.uint8)
                    .at[key_v, key_c].set(1, mode="drop"))
            cand = jnp.where((forb == 0) & (value > 0), value, _INT32_MAX)
            return cand.min(axis=1)[:V].astype(jnp.int32)

        return mex


@dataclasses.dataclass(frozen=True)
class EllPallasMexBackend(MexBackend):
    """The Pallas TPU ``firstfit`` bitmask kernel, fed by an O(E) scatter of
    the per-round edge contributions into the graph's ELL (row, slot)
    geometry. 'Regularize, then go fast' (DESIGN.md §2): the irregular part
    is one XLA scatter; the kernel consumes a dense [V, D] slab in VMEM.
    """

    name = "ell_pallas"
    needs_ell = True
    needs_color_bound = True
    words: Optional[int] = None
    interpret: Optional[bool] = None

    def bind(self, *, num_vertices: int, max_colors: int = 0,
             ell_slot=None, ell_width: int = 0, max_degree: int = -1) -> MexFn:
        from ..kernels.ops import resolve_interpret  # deferred: core importable solo

        if ell_slot is None:
            raise ValueError(
                "ell_pallas engine needs the ELL layout: build the graph "
                "with Graph.to_device(layout='ell') (or compute edge_slots "
                "for a custom partition)")
        # completeness is judged against the TRUE max degree (not the
        # possibly color_bound-capped max_colors): a truncated ELL layout
        # (to_device(ell_width=...) below the max degree) drops forbids in
        # the slab scatter and would silently corrupt colorings
        required = max_degree if max_degree >= 0 else max_colors - 1
        if required > 0 and ell_width < required:
            raise ValueError(
                f"ell_pallas engine: ELL width {ell_width} is below the "
                f"graph's max degree {required}; rebuild with "
                "Graph.to_device(layout='ell') (full width)")
        V = num_vertices
        D = max(1, int(ell_width))
        words = _resolve_words(self.words, max_colors, self.name)
        interp = resolve_interpret(self.interpret)
        from ..kernels.firstfit import firstfit

        def mex(key_v, key_c):
            slab = (jnp.zeros((V + 1, D), jnp.int32)
                    .at[key_v, ell_slot].set(key_c, mode="drop"))
            return firstfit(slab[:V], words=words, interpret=interp)

        return mex

    def bind_slab(self, *, capacity: int, max_colors: int = 0,
                  ell_width: int = 0, max_degree: int = -1) -> SlabMexFn:
        """Frontier bind: the kernel consumes a compacted (capacity, D) ELL
        slab scattered through the per-round ``slot`` operand — no static
        ell_slot needed, the compaction computes row positions itself."""
        from ..kernels.firstfit import firstfit
        from ..kernels.ops import resolve_interpret

        D = max(1, int(ell_width if ell_width > 0 else max_degree))
        if max_degree > D:
            raise ValueError(
                f"ell_pallas slab bind: width {D} is below the graph's max "
                f"degree {max_degree}; a frontier row would drop forbids")
        words = _resolve_words(self.words, max_colors, self.name)
        interp = resolve_interpret(self.interpret)
        cap = int(capacity)

        def mex(key_v, key_c, slot):
            slab = (jnp.zeros((cap + 1, D), jnp.int32)
                    .at[key_v, slot].set(key_c, mode="drop"))
            return firstfit(slab[:cap], words=words, interpret=interp)

        return mex


@dataclasses.dataclass(frozen=True)
class FusedPallasMexBackend(MexBackend):
    """The Pallas ``round_fused`` kernel (kernels/round_fused.py, DESIGN.md
    §FusedRound): the ``firstfit`` bitmask mex PLUS the Alg. 2 conflict
    predicate in ONE read of the ELL slab. Per-round contributions scatter
    into the packed int32 entry slab (color | FORBID bit) exactly like the
    ``ell_pallas`` scatter — the engine protocol pre-masks sweeps by
    precedence, so the drivers consume only the mex lane here (the conflict
    lane stays inert: no CONFLICT bits are packed and ``own_colors`` is 0).
    Its share of the HBM roofline on the chip is the benchmark's
    ``round_fused_roofline`` (``bench/``).

    A plan whose max degree is above :data:`HUB_WIDTH` lays its graphs out
    binned (:meth:`layout_width`): the slab is ``HUB_WIDTH`` wide, and
    every edge of a longer row carries the marker slot ``HUB_WIDTH``, which
    the slab scatter drops. Those hub rows take the per-edge
    :func:`repro.core.mex.segment_mex` (``hub=True``) instead, and a row's
    mex is the larger of the two paths' (the path a row does not take reads
    1). So the
    slab, the kernel's bitset and the host slot map are sized from the
    bin, not from the max degree.

    Bit-identical to ``"bitmap"``/``"ell_pallas"`` by construction: same
    forbidden bitset (color 0 pre-set, out-of-range colors drop — a slab
    row's mex is at most its width + 1), same min-free-bit scan.
    """

    name = "fused_pallas"
    needs_ell = True
    needs_color_bound = True
    words: Optional[int] = None
    interpret: Optional[bool] = None

    def layout_width(self, max_degree: int) -> int:
        return max(1, min(int(max_degree), HUB_WIDTH))

    def _slab_width(self, width: int, max_degree: int, what: str) -> int:
        """The slab width of a layout ``width`` wide, checked: the full
        max degree, or this engine's bin below it. A narrower slab of any
        other width would have been truncated, not binned."""
        D = max(1, int(width))
        if max_degree > D and D != self.layout_width(max_degree):
            raise ValueError(
                f"fused_pallas {what}: ELL width {D} is below the graph's "
                f"max degree {max_degree} and is not the engine's bin "
                f"width {self.layout_width(max_degree)}; rebuild with "
                "Graph.to_device(layout='ell') (full width)")
        return D

    def bind(self, *, num_vertices: int, max_colors: int = 0,
             ell_slot=None, ell_width: int = 0, max_degree: int = -1) -> MexFn:
        from ..kernels.ops import resolve_interpret  # deferred: core importable solo

        if ell_slot is None:
            raise ValueError(
                "fused_pallas engine needs the ELL layout: build the graph "
                "with Graph.to_device(layout='ell') (or compute edge_slots "
                "for a custom partition)")
        required = max_degree if max_degree >= 0 else max_colors - 1
        V = num_vertices
        D = self._slab_width(ell_width, required, "bind")
        hub = required > D
        words = _resolve_words(self.words, min(max_colors, D + 1) if hub
                               else max_colors, self.name)
        interp = resolve_interpret(self.interpret)
        from ..kernels.round_fused import FORBID_BIT, round_fused

        def mex(key_v, key_c):
            ent = (jnp.zeros((V + 1, D), jnp.int32)
                   .at[key_v, ell_slot].set(key_c | FORBID_BIT, mode="drop"))
            m, _ = round_fused(ent[:V], jnp.zeros((V,), jnp.int32),
                               words=words, interpret=interp)
            if hub:
                m = jnp.maximum(m, segment_mex(
                    jnp.where(ell_slot == D, key_v, V), key_c, V, hub=True))
            return m

        return mex

    def bind_slab(self, *, capacity: int, max_colors: int = 0,
                  ell_width: int = 0, max_degree: int = -1) -> SlabMexFn:
        """Frontier bind: the compacted (capacity, D) entry slab scatters
        through the per-round ``slot`` operand, mirroring the ell_pallas
        slab bind. Below the max degree, an edge whose ``slot`` is the
        width belongs to a hub row and takes the per-edge path."""
        from ..kernels.ops import resolve_interpret
        from ..kernels.round_fused import FORBID_BIT, round_fused

        D = self._slab_width(ell_width if ell_width > 0 else max_degree,
                             max_degree, "slab bind")
        hub = max_degree > D
        words = _resolve_words(self.words, min(max_colors, D + 1) if hub
                               else max_colors, self.name)
        interp = resolve_interpret(self.interpret)
        cap = int(capacity)

        def mex(key_v, key_c, slot):
            ent = (jnp.zeros((cap + 1, D), jnp.int32)
                   .at[key_v, slot].set(key_c | FORBID_BIT, mode="drop"))
            m, _ = round_fused(ent[:cap], jnp.zeros((cap,), jnp.int32),
                               words=words, interpret=interp)
            if hub:
                m = jnp.maximum(m, segment_mex(
                    jnp.where(slot == D, key_v, cap), key_c, cap, hub=True))
            return m

        return mex


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------
_REGISTRY: Dict[str, MexBackend] = {}

EngineSpec = Union[str, MexBackend]


def register_backend(backend: MexBackend, *, overwrite: bool = False) -> MexBackend:
    """Register a backend instance under ``backend.name`` so every driver
    accepts it via ``engine="<name>"``."""
    if backend.name in _REGISTRY and not overwrite:
        raise ValueError(f"mex backend {backend.name!r} already registered")
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(engine: EngineSpec) -> MexBackend:
    """Resolve ``engine=`` — a registered name or a MexBackend instance —
    with its call-time defaults decided (:meth:`MexBackend.resolved`)."""
    if isinstance(engine, MexBackend):
        return engine.resolved()
    try:
        return _REGISTRY[engine].resolved()
    except KeyError:
        raise ValueError(
            f"unknown mex backend {engine!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


register_backend(SortMexBackend())
register_backend(BitmapMexBackend())
register_backend(EllPallasMexBackend())
register_backend(FusedPallasMexBackend())


# --------------------------------------------------------------------------
# the shared speculation machinery
# --------------------------------------------------------------------------
def edge_slots(src: jnp.ndarray, num_vertices: int) -> jnp.ndarray:
    """Per-edge slot within its vertex row, for row-contiguous edge lists
    (CSR order — true of DeviceGraph edge lists and partition_graph slabs).

    Device-side counterpart of the host ELL construction; lets the
    distributed driver bind the ``ell_pallas`` engine to a local slab
    without materializing a host ELL."""
    m = src.shape[0]
    idx = jnp.arange(m, dtype=jnp.int32)
    first = (jnp.full((num_vertices + 1,), m, jnp.int32)
             .at[jnp.minimum(src, num_vertices)].min(idx))
    return idx - first[jnp.minimum(src, num_vertices)]


def fixpoint_iterate(update, x0, *, max_iters, wrap=lambda x: x):
    """Chaotic iteration x <- update(x) to a fixpoint (or ``max_iters``).

    ``wrap`` tags the loop-carried scalars for the execution context (the
    distributed driver passes ``lax.pvary`` so the carriers type-check
    under shard_map). Returns (x, iters, still_changing)."""

    def body(state):
        x, _, n = state
        xn = update(x)
        return xn, jnp.any(xn != x), n + 1

    def cond(state):
        _, changed, n = state
        return jnp.logical_and(changed, n < max_iters)

    x, changed, n = lax.while_loop(
        cond, body,
        (x0, wrap(jnp.asarray(True)), wrap(jnp.asarray(0, jnp.int32))))
    return x, n, changed


def fixpoint_sweep(mex: MexFn, spec: SweepSpec, colors0: jnp.ndarray,
                   pending: jnp.ndarray, *, max_sweeps: int,
                   wrap=lambda x: x):
    """THE speculative inner loop (paper Alg. 2 phase 1 / Alg. 3-5): sweep
        c[v] <- mex{ contribution(e) : e forbids v }     for pending v
    to its fixpoint. ITERATIVE, DATAFLOW and the distributed local solve
    all call this — their differences live entirely in ``spec``.

    Returns (colors, sweeps, still_changing).

    The padded color vector is loop-carried state: the phantom slot V is
    written once at entry and every sweep updates the V-prefix in place
    (one dynamic-update-slice), instead of re-materializing the [V+1]
    concatenation per iteration."""
    V = colors0.shape[0]

    def sweep(cpad):
        key_c = jnp.where(spec.dyn, cpad[spec.dyn_idx], spec.static_c)
        new = jnp.where(pending, mex(spec.key_v, key_c), cpad[:V])
        return cpad.at[:V].set(new)

    cpad0 = jnp.concatenate([colors0, jnp.zeros((1,), jnp.int32)])
    cpad, n, changed = fixpoint_iterate(sweep, cpad0, max_iters=max_sweeps,
                                        wrap=wrap)
    return cpad[:V], n, changed


def lockstep_offsets(pending: jnp.ndarray, concurrency: int) -> jnp.ndarray:
    """OpenMP-static superstep offsets over the pending set: rank within the
    pending set mod block size (paper Alg. 2's thread-block geometry)."""
    r = pending.sum(dtype=jnp.int32)
    bs = lax.div(r + concurrency - 1, concurrency)
    rank = jnp.cumsum(pending.astype(jnp.int32)) - 1
    return jnp.where(pending, rank % jnp.maximum(bs, 1), 0).astype(jnp.int32)


def speculation_conflicts(src: jnp.ndarray, dst: jnp.ndarray,
                          colors: jnp.ndarray, pending: jnp.ndarray,
                          num_vertices: int) -> jnp.ndarray:
    """Alg. 2 phase 2 on an edge list: monochromatic same-round pairs queue
    the higher-index endpoint. Returns the next round's pending mask.

    (The distributed driver keeps its own fused variant — its conflict view
    decodes from the packed wire gather, a genuinely per-machine
    specialization; see distributed.py §Perf H-C1.)"""
    cpad = jnp.concatenate([colors, jnp.zeros((1,), jnp.int32)])
    ppad = jnp.concatenate([pending, jnp.zeros((1,), jnp.bool_)])
    conf_e = ppad[src] & ppad[dst] & (cpad[src] == cpad[dst]) & (src > dst)
    return (jnp.zeros((num_vertices,), jnp.int32)
            .at[src].max(conf_e.astype(jnp.int32), mode="drop")
            .astype(jnp.bool_))
