"""The front door: ``ColoringSpec`` -> compiled ``ColoringPlan`` -> unified
``ColoringReport`` (DESIGN.md §API).

The paper's thesis is that ONE speculate-then-resolve scheme spans radically
different machines once the machine-specific pieces are pluggable. PR 1 made
the *mex inner loop* pluggable (:class:`repro.core.engine.MexBackend`); this
module does the same for the *algorithms*: the three drivers become
registered :class:`ColoringStrategy` instances behind one declarative entry
point, so every cross-cutting axis (engine, model, ordering, bounds) is
threaded once — here — instead of once per driver.

Three ways in, strictest first:

* ``color(graph, spec)`` — one-shot: resolve the spec, run the strategy,
  return a :class:`ColoringReport`. The ergonomic path; compiles per call
  shape like the legacy functions.
* ``compile_plan(spec, graph_or_shape)`` -> :class:`ColoringPlan` — the
  compile-once, color-many path the serving roadmap needs. The plan lowers
  the model, binds the mex backend, fixes every static shape (vertex count,
  bucket-padded edge capacity, color capacity) and jit-specializes ONCE;
  ``plan(graph)`` then serves **any same-bucket graph with zero retrace**
  (:func:`repro.core.graph.pad_bucket` quantizes edge counts so "same
  shape" is achievable in practice), and ``plan.map(graphs)`` vmaps a batch
  through one program for throughput.
* the legacy ``color_iterative`` / ``color_dataflow`` / ``color_distributed``
  functions — thin back-compat shims over the same registry (bit-identical
  results; see iterative.py / dataflow.py / distributed.py).

Orderings (paper §5.1, ``repro.core.ordering.ORDERINGS``) are applied by
relabeling the *constraint* graph before coloring and un-relabeling the
colors on the way out — reports are **always in original vertex ids**, for
every model (under ``d2``/``pd2`` the ordering ranks constraint-graph
degrees, which is the quantity that matters for D2 color quality).

Registering a new algorithm (Rokos-style detect-and-recolor, a distributed
recoloring pass, ...) is a :class:`ColoringStrategy` subclass plus one
:func:`register_strategy` call — the spec/plan/report plumbing, ordering,
model lowering and batching come for free.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, NamedTuple, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from .distance2 import MODELS, as_constraint_graph, constraint_host_graph
from .distributed import PARTITION_SCHEMES
from .engine import EngineSpec, MexBackend, get_backend
from .frontier import FRONTIER_MODES, frontier_capacities, resolve_frontier
from .graph import BipartiteGraph, DeviceGraph, Graph, pad_bucket
from .ordering import ORDERINGS
from . import spans

_LOWERINGS = ("auto", "wedge", "square")
WIRE_MODES = ("auto", "boundary", "full")


# --------------------------------------------------------------------------
# the spec
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ColoringSpec:
    """Declarative description of a coloring run: *what* to compute and on
    *which* machinery — everything :func:`compile_plan` needs to specialize
    a program, and nothing data-dependent.

    strategy     registered :class:`ColoringStrategy` name (or instance):
                 ``"iterative"`` | ``"dataflow"`` | ``"distributed"``;
    model        coloring semantics: ``"d1"`` | ``"d2"`` | ``"pd2"``
                 (repro.core.distance2);
    engine       first-fit mex backend name/instance (repro.core.engine);
    ordering     vertex-visit priority, a ``repro.core.ordering.ORDERINGS``
                 key — applied as a relabeling of the constraint graph,
                 un-applied on the way out (reports stay in original ids);
    ordering_seed  seed for stochastic orderings (``"random"``);
    lowering     D2/PD2 constraint lowering: ``"auto"`` | ``"wedge"`` |
                 ``"square"`` (distance2.py; plans always use the dedup'd
                 square lowering so shapes are paddable);
    side         the colored class under ``model="pd2"``;
    concurrency  ITERATIVE's lockstep virtual-thread count;
    max_rounds / max_sweeps / color_bound  as on the legacy drivers;
    mesh         jax Mesh for the distributed strategy (None = 1-device);
    local_concurrency  distributed per-device concurrency (C=1 is the
                 classic Bozdag scheme);
    frontier     active-set execution (repro.core.frontier): ``"auto"``
                 (compact rounds >= 1 whenever the graph carries the
                 incident-edge auxiliary — the default), ``"on"`` (require
                 it), ``"off"`` (full sweeps every round). Bit-identical
                 results either way — the frontier is an execution bypass,
                 never a semantics change;
    frontier_capacity  static vertex-slab capacity override (0 = the
                 |V|/32 bucket ladder; the edge slab scales with it);
    wire         the distributed per-round exchange: ``"auto"`` (boundary
                 wire; a plan whose served graph overflows the pinned halo
                 capacity spills to a lazily-compiled full-gather program),
                 ``"boundary"`` (require the boundary wire — halo overflow
                 raises), ``"full"`` (the legacy ``[Vp]`` gather, kept as
                 the parity oracle). All three are bit-identical;
    partition    distributed vertex ownership: ``"1d"`` contiguous blocks
                 or ``"2d"`` block-cyclic over a device grid (spreads
                 R-MAT hub regions — repro.core.distributed).
    """

    strategy: Union[str, "ColoringStrategy"] = "iterative"
    model: str = "d1"
    engine: EngineSpec = "sort"
    ordering: str = "natural"
    ordering_seed: int = 0
    lowering: str = "auto"
    side: str = "left"
    concurrency: int = 64
    max_rounds: int = 64
    max_sweeps: int = 4096
    color_bound: int = 0
    mesh: Optional[object] = None  # jax.sharding.Mesh; object keeps the
    # dataclass importable without touching jax.sharding at class-def time
    local_concurrency: int = 1
    frontier: str = "auto"
    frontier_capacity: int = 0
    wire: str = "auto"
    partition: str = "1d"

    def __post_init__(self):
        if self.model not in MODELS:
            raise ValueError(f"unknown coloring model {self.model!r}; "
                             f"choose from {MODELS}")
        if self.lowering not in _LOWERINGS:
            raise ValueError(f"unknown lowering {self.lowering!r}; "
                             f"choose from {_LOWERINGS}")
        if self.frontier not in FRONTIER_MODES:
            raise ValueError(f"unknown frontier mode {self.frontier!r}; "
                             f"choose from {FRONTIER_MODES}")
        if self.wire not in WIRE_MODES:
            raise ValueError(f"unknown wire mode {self.wire!r}; "
                             f"choose from {WIRE_MODES}")
        if self.partition not in PARTITION_SCHEMES:
            raise ValueError(f"unknown partition scheme {self.partition!r}; "
                             f"choose from {PARTITION_SCHEMES}")

    def resolve(self) -> Tuple["ColoringStrategy", MexBackend]:
        """Resolve the registered pieces (strategy, mex backend) by name."""
        return get_strategy(self.strategy), get_backend(self.engine)

    def to_dict(self) -> dict:
        """JSON-able export (the checkpoint/restore wire format): every
        field by registry *name*, so a restored process resolves them
        against its own registries. Mesh-bound specs are process-local
        (device handles don't serialize) and are rejected."""
        if self.mesh is not None:
            raise ValueError(
                "mesh-bound specs are process-local and cannot be "
                "serialized; rebuild the spec with the restoring "
                "process's mesh instead")
        d = {f.name: getattr(self, f.name)
             for f in dataclasses.fields(self) if f.name != "mesh"}
        if not isinstance(d["strategy"], str):
            d["strategy"] = get_strategy(d["strategy"]).name
        if not isinstance(d["engine"], str):
            d["engine"] = get_backend(d["engine"]).name
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "ColoringSpec":
        """Inverse of :meth:`to_dict` — unknown keys are rejected by the
        dataclass constructor, so a stale checkpoint fails loudly."""
        return cls(**d)


# --------------------------------------------------------------------------
# the report
# --------------------------------------------------------------------------
class RawColoring(NamedTuple):
    """What every strategy returns (a pytree, so it flows through jit/vmap):
    colors in the *strategy's* label space, per-round histories, and an
    unconverged flag. :class:`ColoringPlan`/:func:`color` normalize it into
    a :class:`ColoringReport` (un-relabeling, host transfer, wall time)."""

    colors: jnp.ndarray               # [V] int32 >= 1
    rounds: jnp.ndarray               # scalar int32
    conflicts_per_round: jnp.ndarray  # [max_rounds] int32
    sweeps_per_round: jnp.ndarray     # [max_rounds] int32
    unconverged: jnp.ndarray          # scalar bool
    frontier_per_round: jnp.ndarray   # [max_rounds] int32: active vertices
    # compacted in each round (0 = the round took the full-edge path; for
    # DATAFLOW, entry 0 counts the slab-compacted sweeps instead)
    wavefront_spills: jnp.ndarray = 0  # scalar int32: offset classes of the
    # full rounds whose forbidding edges overflowed the lockstep wavefront's
    # window and took a full-edge pass (ITERATIVE; 0 elsewhere)


def _invert_order(order: np.ndarray) -> np.ndarray:
    """``order[k]`` = vertex visited k-th -> ``perm[v]`` = new id of vertex
    v (the relabel argument of :meth:`Graph.relabel`; ``colors[perm]`` is
    the exact inverse on the way out)."""
    perm = np.empty_like(order)
    perm[order] = np.arange(order.shape[0], dtype=order.dtype)
    return perm


def _build_report(raw: "RawColoring", spec: "ColoringSpec",
                  strategy_name: str, perm: Optional[np.ndarray],
                  t0: float, *, batch_denom: int = 1) -> "ColoringReport":
    """Normalize a strategy's RawColoring into the unified report: raise on
    non-convergence, un-relabel to original vertex ids, trim histories,
    stamp (amortized) wall time. The one place this logic lives — both the
    one-shot :func:`color` path and :class:`ColoringPlan` route here."""
    if bool(raw.unconverged):
        raise RuntimeError(
            f"{strategy_name} did not converge within "
            f"max_rounds={spec.max_rounds} / max_sweeps={spec.max_sweeps}")
    colors = np.asarray(raw.colors)
    if perm is not None:
        colors = colors[perm]  # back to original vertex ids
    rounds = int(raw.rounds)
    return ColoringReport(
        colors=colors, rounds=rounds,
        conflicts_per_round=np.asarray(raw.conflicts_per_round)[:rounds],
        sweeps_per_round=np.asarray(raw.sweeps_per_round)[:rounds],
        frontier_sizes_per_round=(
            np.asarray(raw.frontier_per_round)[:rounds]),
        wavefront_spills=int(raw.wavefront_spills),
        wall_time_s=(time.perf_counter() - t0) / max(1, batch_denom),
        spec=spec)


def _trivial_report(spec: "ColoringSpec", num_vertices: int, t0: float, *,
                    batch_denom: int = 1,
                    colors: Optional[np.ndarray] = None) -> "ColoringReport":
    """The degenerate result (V=0, or no constraint edges at all): every
    vertex takes color 1 — vacuously valid — in zero rounds. The engines
    never run, so no phantom slab is ever allocated. ``colors`` preserves
    a recolor warm start: committed (positive) entries pass through
    untouched — any positive coloring is valid without constraints — and
    only uncolored slots take color 1."""
    if colors is not None:
        carried = np.asarray(colors).astype(np.int32)
        carried = np.where(carried > 0, carried, 1).astype(np.int32)
    else:
        carried = np.ones(num_vertices, np.int32)
    empty = np.zeros(0, np.int32)
    return ColoringReport(
        colors=carried, rounds=0,
        conflicts_per_round=empty, sweeps_per_round=empty.copy(),
        frontier_sizes_per_round=empty.copy(),
        wall_time_s=(time.perf_counter() - t0) / max(1, batch_denom),
        spec=spec)


def _graph_extent(g, spec: "ColoringSpec") -> Tuple[int, int]:
    """(colored-class size, raw edge count) of an input graph, readable
    without lowering the coloring model — the degenerate-input check."""
    if isinstance(g, BipartiteGraph):
        n = g.num_left if spec.side == "left" else g.num_right
        return n, g.num_edges
    return g.num_vertices, g.num_directed_edges


@dataclasses.dataclass
class ColoringReport:
    """The one result type every strategy produces.

    ``colors`` is a host int32 array **in original vertex ids** (any
    ``ordering`` relabeling is undone). Histories are trimmed to ``rounds``
    entries. ``frontier_sizes_per_round[r]`` is the number of active
    vertices round r swept through the compacted frontier slab (0 = the
    round took the full-edge path; DATAFLOW reports its slab-compacted
    sweep count in entry 0). ``sweeps_per_round[r]`` counts round r's
    phase-1 passes over its edge set: a chaotic sweep is one; a lockstep
    wavefront (ITERATIVE's full rounds, repro.core.iterative) is one plus
    one full-edge pass per spilled offset class, and ``wavefront_spills``
    sums those classes over the rounds. ``wall_time_s`` covers lowering + execution +
    host transfer: for ``plan(g)`` it is the ``plan.call`` span's duration
    (:mod:`repro.core.spans`); plan-batched runs report the amortized
    per-graph time.

    Summary scalars (``num_colors``, ``total_conflicts``, ``sweeps``) are
    memoized — reports get re-summarized in benchmark/serving loops, and a
    distinct-count over a large coloring is not free. ``num_colors`` is
    the number of DISTINCT positive colors, not ``colors.max()``:
    recolor/delete paths legitimately leave palette gaps."""

    colors: np.ndarray
    rounds: int
    conflicts_per_round: np.ndarray
    sweeps_per_round: np.ndarray
    wall_time_s: float
    spec: ColoringSpec
    frontier_sizes_per_round: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int32))
    wavefront_spills: int = 0

    @functools.cached_property
    def num_colors(self) -> int:
        # distinct positive colors, NOT colors.max(): recolor/delete paths
        # leave palette gaps, and the max would overstate the count
        from .metrics import num_colors as _distinct
        return _distinct(self.colors)

    @functools.cached_property
    def total_conflicts(self) -> int:
        return int(self.conflicts_per_round.sum())

    @functools.cached_property
    def sweeps(self) -> int:
        return int(self.sweeps_per_round.sum())

    def __repr__(self) -> str:  # compact: reports get printed in loops
        s = self.spec
        return (f"ColoringReport(strategy={s.strategy!r}, model={s.model!r}, "
                f"colors={self.num_colors}, rounds={self.rounds}, "
                f"sweeps={self.sweeps}, conflicts={self.total_conflicts}, "
                f"wall_time_s={self.wall_time_s:.4f})")


# --------------------------------------------------------------------------
# the strategy layer
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ColoringStrategy:
    """Base class: a named, registered coloring algorithm.

    A strategy supplies ONE thing: how to turn a constraint
    :class:`DeviceGraph` into a :class:`RawColoring`
    (:meth:`device_program`). The base class derives everything else —
    one-shot execution over the legacy lowering path (:meth:`oneshot`),
    plan compilation with a trace-counting jit wrapper (:meth:`compile`),
    and vmapped batching (:meth:`compile_batched`). Host-level strategies
    (the distributed BSP driver partitions on host) override
    :meth:`compile`/:meth:`oneshot` wholesale and set ``wants = "host"``.
    """

    name = "abstract"
    supports_map = True    # plan.map() batching via vmap
    wants = "device"       # "device": executor consumes a DeviceGraph;
                           # "host": executor consumes the host constraint
                           # Graph (strategies that partition themselves)

    # -- the one required hook -------------------------------------------
    def device_program(self, spec: ColoringSpec,
                       backend: MexBackend) -> Callable[[DeviceGraph], RawColoring]:
        raise NotImplementedError

    # -- derived machinery ------------------------------------------------
    def oneshot(self, spec: ColoringSpec, g) -> RawColoring:
        """Run once on ``g`` exactly as the legacy driver would: same model
        lowering (wedge-by-default for d2/pd2), same jit cache, no padding.
        The legacy shims and :func:`color` route through this."""
        backend = get_backend(spec.engine)
        dg = as_constraint_graph(g, spec.model, needs_ell=backend.needs_ell,
                                 strategy=spec.lowering, side=spec.side)
        return self.device_program(spec, backend)(dg)

    def plan_state(self, spec: ColoringSpec, statics: "PlanShape",
                   **runtime) -> Tuple:
        """Normalize per-call runtime state (``plan(g, key=value, ...)``)
        into the extra arguments this strategy's compiled program takes,
        as host arrays (the plan uploads them with the graph). The base
        strategies are stateless — any runtime kwarg is an error; the
        ``"recolor"`` strategy overrides this to accept the (colors, seed)
        warm-start pair. Shapes derive from ``statics`` only, so state
        never breaks the zero-retrace guarantee."""
        if runtime:
            raise TypeError(
                f"strategy {self.name!r} takes no per-call state; got "
                f"{sorted(runtime)}")
        return ()

    def compile(self, spec: ColoringSpec, statics: "PlanShape",
                trace_hook: Callable[[], None]) -> Callable:
        """Plan-time compilation: one jitted program over the canonical
        (bucket-padded) DeviceGraph (plus any :meth:`plan_state` extras).
        ``trace_hook`` runs at trace time only — the plan counts traces
        with it, and tests assert the count stays at one across
        same-bucket graphs."""
        prog = self.device_program(spec, get_backend(spec.engine))

        def run(dg, *state):
            trace_hook()
            return prog(dg, *state)

        return jax.jit(run)

    def compile_batched(self, spec: ColoringSpec, statics: "PlanShape",
                        trace_hook: Callable[[], None]) -> Callable:
        """The ``plan.map`` program: the same per-graph program vmapped over
        a stacked batch of canonical DeviceGraphs."""
        prog = self.device_program(spec, get_backend(spec.engine))

        def run(dg):
            trace_hook()
            return prog(dg)

        return jax.jit(jax.vmap(run))


_REGISTRY: Dict[str, ColoringStrategy] = {}

StrategySpec = Union[str, ColoringStrategy]


def register_strategy(strategy: ColoringStrategy, *,
                      overwrite: bool = False) -> ColoringStrategy:
    """Register a strategy instance under ``strategy.name`` so every spec
    resolves it via ``strategy="<name>"`` (mirror of
    :func:`repro.core.engine.register_backend`)."""
    if strategy.name in _REGISTRY and not overwrite:
        raise ValueError(f"coloring strategy {strategy.name!r} already "
                         "registered")
    _REGISTRY[strategy.name] = strategy
    return strategy


def get_strategy(strategy: StrategySpec) -> ColoringStrategy:
    """Resolve ``strategy`` — a registered name or an instance."""
    if isinstance(strategy, ColoringStrategy):
        return strategy
    try:
        return _REGISTRY[strategy]
    except KeyError:
        raise ValueError(
            f"unknown coloring strategy {strategy!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def available_strategies() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# --------------------------------------------------------------------------
# the three shipped strategies
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class IterativeStrategy(ColoringStrategy):
    """The paper's Algorithm 2 (speculation + iteration) — iterative.py."""

    name = "iterative"

    def device_program(self, spec, backend):
        from .iterative import _iterative_impl

        def run(dg):
            fcv, fce = resolve_frontier(
                spec.frontier, int(spec.frontier_capacity),
                num_vertices=dg.num_vertices, padded_edges=dg.padded_edges,
                max_degree=dg.max_degree, has_inc=dg.has_frontier)
            colors, rnd, conf, sweeps, fronts, left, spills = _iterative_impl(
                dg, concurrency=int(spec.concurrency),
                max_rounds=int(spec.max_rounds),
                max_sweeps=int(spec.max_sweeps), backend=backend,
                color_bound=int(spec.color_bound),
                frontier_cap_v=fcv, frontier_cap_e=fce)
            return RawColoring(colors, rnd, conf, sweeps, left, fronts,
                               spills)

        return run


@dataclasses.dataclass(frozen=True)
class DataflowStrategy(ColoringStrategy):
    """The paper's Algorithms 3-5 as a chaotic fixpoint — dataflow.py.
    One conflict-free speculative round; ``sweeps_per_round`` holds the
    DAG-depth sweep count."""

    name = "dataflow"

    def device_program(self, spec, backend):
        from .dataflow import _dataflow_impl

        def run(dg):
            fcv, fce = resolve_frontier(
                spec.frontier, int(spec.frontier_capacity),
                num_vertices=dg.num_vertices, padded_edges=dg.padded_edges,
                max_degree=dg.max_degree, has_inc=dg.has_frontier)
            colors, n, changed, nslab = _dataflow_impl(
                dg, max_sweeps=int(spec.max_sweeps), backend=backend,
                color_bound=int(spec.color_bound),
                frontier_cap_v=fcv, frontier_cap_e=fce)
            return RawColoring(colors, jnp.asarray(1, jnp.int32),
                               jnp.zeros((1,), jnp.int32),
                               jnp.reshape(n, (1,)).astype(jnp.int32),
                               changed,
                               jnp.reshape(nslab, (1,)).astype(jnp.int32))

        return run


def _shard_slabs(mesh, *slabs: np.ndarray) -> list:
    """Upload ``[D, ...]`` per-shard host slabs row-sharded over every
    mesh axis, so each device receives only its own row (the layout the
    shard_map program's in_specs expect — no reshard inside the jit)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = NamedSharding(mesh, P(tuple(mesh.axis_names), None))
    return [jax.device_put(s, sharding) for s in slabs]


@dataclasses.dataclass(frozen=True)
class DistributedStrategy(ColoringStrategy):
    """The Bozdag-style BSP driver over a jax mesh — distributed.py. A host
    strategy: it partitions the constraint graph itself, so plans hand it
    the host graph and it manages its own (slab-shaped) jit program.
    ``plan.map`` is unsupported (one mesh program is already the batch)."""

    name = "distributed"
    supports_map = False
    wants = "host"

    @staticmethod
    def _mesh(spec: ColoringSpec):
        if spec.mesh is not None:
            return spec.mesh
        from jax.sharding import Mesh
        return Mesh(np.asarray(jax.devices()[:1]), ("x",))

    def _build(self, spec: ColoringSpec, mesh, *, verts_local: int,
               edges_local: int, max_colors: int, ell_width: int,
               wire: str = "boundary", wire_colors: int = 0):
        from .distributed import build_distributed_coloring
        fcv = fce = 0
        if spec.frontier != "off":
            # per-shard slabs: the BSP driver recovers incident-edge
            # pointers on device, so the frontier is always available here
            fcv, fce = frontier_capacities(
                verts_local, edges_local, ell_width,
                capacity=int(spec.frontier_capacity))
        return build_distributed_coloring(
            mesh, verts_local, edges_local,
            local_concurrency=int(spec.local_concurrency),
            max_rounds=int(spec.max_rounds),
            max_sweeps=int(spec.max_sweeps),
            engine=spec.engine, max_colors=max_colors, ell_width=ell_width,
            frontier_cap_v=fcv, frontier_cap_e=fce,
            wire=wire, wire_colors=wire_colors)

    def _raw(self, spec: ColoringSpec, num_vertices: int, colors, rounds,
             conf, sweeps, fronts) -> RawColoring:
        colors = np.asarray(colors).reshape(-1)[:num_vertices]
        rounds = int(rounds)
        conf = np.asarray(conf)
        unconverged = bool(rounds >= int(spec.max_rounds)
                           and rounds > 0 and conf[rounds - 1] > 0)
        return RawColoring(colors, np.int32(rounds), conf, np.asarray(sweeps),
                           np.bool_(unconverged), np.asarray(fronts))

    def oneshot(self, spec: ColoringSpec, g) -> RawColoring:
        from jax import set_mesh
        from .distributed import partition_graph
        host = constraint_host_graph(g, spec.model, side=spec.side)
        mesh = self._mesh(spec)
        D = int(np.prod(mesh.devices.shape))
        layout = partition_graph(host, D, scheme=spec.partition)
        max_colors = host.max_degree() + 1
        if spec.color_bound > 0:
            max_colors = min(max_colors, int(spec.color_bound))
        # one-shot slabs fit the graph exactly, so "auto" never spills
        wire = "full" if spec.wire == "full" else "boundary"
        fn = self._build(spec, mesh, verts_local=layout.verts_local,
                         edges_local=layout.edges_local,
                         max_colors=max_colors, ell_width=host.max_degree(),
                         wire=wire, wire_colors=host.max_degree() + 1)
        with set_mesh(mesh):
            colors, rounds, conf, sweeps, fronts = fn(
                *_shard_slabs(mesh, layout.lsrc, layout.ldst, layout.bnd))
        colors = layout.unpermute(np.asarray(colors).reshape(-1))
        return self._raw(spec, host.num_vertices, colors, rounds, conf,
                         sweeps, fronts)

    def compile(self, spec: ColoringSpec, statics: "PlanShape",
                trace_hook: Callable[[], None]) -> Callable:
        from jax import set_mesh
        from .distributed import partition_graph
        mesh = self._mesh(spec)
        D = int(np.prod(mesh.devices.shape))
        Vl = -(-statics.num_vertices // D)
        # slab capacity: even-split share + R-MAT-skew headroom, bucketed —
        # a graph whose densest partition overflows it raises at call time
        slab = pad_bucket(int(-(-statics.padded_edges // D) * 1.35))
        max_colors = statics.max_degree + 1
        if spec.color_bound > 0:
            max_colors = min(max_colors, int(spec.color_bound))
        use_boundary = spec.wire != "full"
        # halo capacity the boundary program pins; _plan_shape derived it
        # from the compile graph (with headroom). wire_colors is the
        # UNCAPPED Delta+1: packed entries must hold any color the solve
        # can assign, and color_bound caps only the forbid tables
        bcap = int(statics.boundary_cap) if use_boundary else 0
        fn = self._build(spec, mesh, verts_local=Vl, edges_local=slab,
                         max_colors=max_colors, ell_width=statics.max_degree,
                         wire=("boundary" if use_boundary else "full"),
                         wire_colors=statics.max_degree + 1)

        def counted(lsrc, ldst, bnd):
            trace_hook()
            return fn(lsrc, ldst, bnd)

        jfn = jax.jit(counted)
        spill: Dict[str, Callable] = {}

        def spill_fn():
            # wire="auto" halo overflow: a lazily-compiled full-gather
            # program (one extra counted trace, ever). Its bnd operand is
            # an ignored [D, 1] dummy so the spill shape is call-invariant.
            if "fn" not in spill:
                f = self._build(spec, mesh, verts_local=Vl, edges_local=slab,
                                max_colors=max_colors,
                                ell_width=statics.max_degree, wire="full",
                                wire_colors=statics.max_degree + 1)

                def counted_full(lsrc, ldst, bnd):
                    trace_hook()
                    return f(lsrc, ldst, bnd)

                spill["fn"] = jax.jit(counted_full)
            return spill["fn"]

        def executor(host: Graph) -> RawColoring:
            layout = partition_graph(host, D, pad_edges_to=slab,
                                     scheme=spec.partition)
            if not use_boundary:
                # the full wire never reads bnd; a fixed dummy keeps the
                # jit signature constant across served graphs
                run = jfn
                bnd = np.full((D, 1), layout.verts_local, np.int32)
            elif layout.boundary_local <= bcap:
                run, bnd = jfn, layout.padded_boundary(bcap)
            elif spec.wire == "boundary":
                raise ValueError(
                    f"graph has {layout.boundary_local} boundary vertices "
                    f"on its densest shard, above the plan halo capacity "
                    f"{bcap}; compile a plan from this graph, or use "
                    "wire='auto' to spill to the full-gather wire")
            else:
                run = spill_fn()
                bnd = np.full((D, 1), layout.verts_local, np.int32)
            with set_mesh(mesh):
                colors, rounds, conf, sweeps, fronts = run(
                    *_shard_slabs(mesh, layout.lsrc, layout.ldst, bnd))
            colors = layout.unpermute(np.asarray(colors).reshape(-1))
            return self._raw(spec, statics.num_vertices, colors, rounds,
                             conf, sweeps, fronts)

        return executor


@dataclasses.dataclass(frozen=True)
class RecolorStrategy(ColoringStrategy):
    """Rokos-style detect-and-recolor (arXiv:1505.04086) as a registered
    strategy — the paper's speculation loop run from a caller-supplied
    warm start instead of the cold (no colors, all pending) one.

    Per-call state rides :meth:`plan_state`: ``plan(g, colors=, seed=)``
    hands the compiled program the committed color vector plus the seed
    mask of vertices to repair (the endpoints of newly conflicting edges,
    under streaming deltas — repro.core.dynamic builds exactly that).
    Phase 1 then recolors ONLY the seed set — committed neighbors forbid
    their colors, so a repaired coloring is valid by the same argument as
    a fresh one — and because the seed is a tiny conflicted tail, round 0
    already takes the compacted frontier path (``seed_frontier``), making
    a delta repair cost O(frontier slab), not O(E).

    With no state supplied (``color(g, strategy="recolor")``, or a bare
    ``plan(g)``), the warm start degenerates to the cold start and the
    strategy is bit-identical to ``"iterative"``. Both arrays are [V] in
    the plan's vertex-id space, so ``ordering`` must stay ``"natural"``
    whenever state is passed. ``plan.map`` is unsupported (delta repairs
    are latency-bound single calls, not throughput batches)."""

    name = "recolor"
    supports_map = False

    def device_program(self, spec, backend):
        from .iterative import _iterative_impl

        def run(dg, colors0=None, pending0=None):
            fcv, fce = resolve_frontier(
                spec.frontier, int(spec.frontier_capacity),
                num_vertices=dg.num_vertices, padded_edges=dg.padded_edges,
                max_degree=dg.max_degree, has_inc=dg.has_frontier)
            colors, rnd, conf, sweeps, fronts, left, spills = _iterative_impl(
                dg, colors0, pending0, concurrency=int(spec.concurrency),
                max_rounds=int(spec.max_rounds),
                max_sweeps=int(spec.max_sweeps), backend=backend,
                color_bound=int(spec.color_bound),
                frontier_cap_v=fcv, frontier_cap_e=fce,
                seed_frontier=True)
            return RawColoring(colors, rnd, conf, sweeps, left, fronts,
                               spills)

        return run

    def plan_state(self, spec, statics, colors=None, seed=None):
        if (colors is not None or seed is not None) \
                and spec.ordering != "natural":
            # cold starts are ordering-invariant (the plan relabels and
            # un-relabels as usual); only a WARM start pins vertex ids
            raise ValueError(
                "recolor repairs an existing coloring in place: state "
                "arrays are in plan vertex ids, so ordering must be "
                "'natural' (got {!r})".format(spec.ordering))
        V = statics.num_vertices
        if colors is None:
            colors_d = np.zeros((V,), np.int32)
        else:
            colors = np.asarray(colors)
            if colors.shape != (V,):
                raise ValueError(f"recolor state: colors shape "
                                 f"{colors.shape} != ({V},)")
            colors_d = colors.astype(np.int32)
        if seed is None:
            seed_d = np.ones((V,), np.bool_)
        else:
            seed = np.asarray(seed)
            if seed.shape != (V,):
                raise ValueError(f"recolor state: seed shape "
                                 f"{seed.shape} != ({V},)")
            seed_d = seed.astype(np.bool_)
        return colors_d, seed_d


register_strategy(IterativeStrategy())
register_strategy(DataflowStrategy())
register_strategy(DistributedStrategy())
register_strategy(RecolorStrategy())


# --------------------------------------------------------------------------
# plans
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PlanShape:
    """The static envelope a :class:`ColoringPlan` specializes on — in
    *constraint-graph* space (after the d2/pd2 lowering, where applicable).

    num_vertices   exact vertex count every served graph must match;
    padded_edges   directed-edge capacity (graphs pad up to it; derived
                   shapes pass through :func:`repro.core.graph.pad_bucket`);
    max_degree     constraint max-degree bound: sizes the table backends'
                   color capacity and the ELL slab width. Graphs above it
                   are rejected (a too-small table silently drops forbids);
    boundary_cap   distributed halo capacity: the per-shard boundary slab
                   width the plan's boundary wire pins (``_plan_shape``
                   derives it, with headroom, by partitioning the compile
                   graph). 0 = no halo slab — correct for device-strategy
                   plans and 1-device meshes; a served graph overflowing
                   the cap spills to the full wire (``wire="auto"``) or is
                   rejected (``wire="boundary"``).
    """

    num_vertices: int
    padded_edges: int
    max_degree: int
    boundary_cap: int = 0


def _plan_shape(spec: ColoringSpec, graph_or_shape) -> PlanShape:
    if isinstance(graph_or_shape, PlanShape):
        return graph_or_shape
    if isinstance(graph_or_shape, DeviceGraph):
        raise TypeError(
            "compile_plan needs a host Graph/BipartiteGraph (plans relabel "
            "and pad on host) or an explicit PlanShape")
    host = constraint_host_graph(graph_or_shape, spec.model, side=spec.side)
    boundary_cap = 0
    if get_strategy(spec.strategy).wants == "host" and spec.wire != "full":
        # halo envelope for the boundary wire: partition the compile graph
        # and give the densest shard's boundary count the same skew
        # headroom as the edge slab, capped at Vl (every vertex boundary)
        from .distributed import partition_graph
        mesh = DistributedStrategy._mesh(spec)
        D = int(np.prod(mesh.devices.shape))
        if D > 1:
            Bl = partition_graph(host, D,
                                 scheme=spec.partition).boundary_local
            if Bl:
                Vl = -(-host.num_vertices // D)
                boundary_cap = min(Vl, pad_bucket(int(Bl * 1.35)))
    return PlanShape(num_vertices=host.num_vertices,
                     padded_edges=pad_bucket(host.num_directed_edges),
                     max_degree=host.max_degree(),
                     boundary_cap=boundary_cap)


class ColoringPlan:
    """A compiled coloring program: spec + static shape envelope, serving
    any same-bucket graph with zero recompilation.

    ``plan(graph)`` -> :class:`ColoringReport`;
    ``plan.map([g0, g1, ...])`` -> list of reports via ONE vmapped program
    (strategies with ``supports_map``).

    ``plan.traces`` counts jit traces of the underlying program(s) — it
    stays at 1 (2 once ``map`` is also used) however many same-bucket
    graphs are served; the test suite pins this. ``plan.backend`` is the
    resolved mex backend the program binds (for the Pallas engines, its
    ``interpret`` says whether the kernel runs compiled or interpreted).
    """

    def __init__(self, spec: ColoringSpec, graph_or_shape):
        self.spec = spec
        self.strategy, self.backend = spec.resolve()
        self.statics = _plan_shape(spec, graph_or_shape)
        if spec.ordering not in ORDERINGS:
            raise ValueError(f"unknown ordering {spec.ordering!r}; "
                             f"choose from {sorted(ORDERINGS)}")
        self._traces = 0
        # a degenerate envelope (no vertices, or no constraint-edge
        # capacity at all) never compiles or runs a program: every served
        # graph is vacuously colored with color 1 — no phantom slabs
        self._degenerate = (self.statics.num_vertices == 0
                            or self.statics.padded_edges == 0)
        self._executor = (None if self._degenerate else
                          self.strategy.compile(spec, self.statics,
                                                self._count_trace))
        self._batched: Optional[Callable] = None

    # ------------------------------------------------------------- internals
    def _count_trace(self):
        self._traces += 1

    @property
    def traces(self) -> int:
        """Number of jit traces taken by this plan's program(s)."""
        return self._traces

    def _canonicalize(self, g) -> Tuple[Graph, Optional[np.ndarray]]:
        """Host graph -> (constraint graph in plan ids, relabel perm or
        None): lowers the model (square lowering: paddable, dedup'd),
        applies the ordering relabel and checks the plan envelope."""
        spec, st = self.spec, self.statics
        host = constraint_host_graph(g, spec.model, side=spec.side)
        if host.num_vertices != st.num_vertices:
            raise ValueError(
                f"plan compiled for {st.num_vertices} vertices, got a graph "
                f"with {host.num_vertices}; compile a new plan")
        perm = None
        if spec.ordering != "natural":
            order = ORDERINGS[spec.ordering](host, spec.ordering_seed)
            perm = _invert_order(order)
            host = host.relabel(perm)
        if host.num_directed_edges > st.padded_edges:
            raise ValueError(
                f"graph has {host.num_directed_edges} constraint edges, "
                f"above the plan bucket {st.padded_edges}; compile a plan "
                "from this graph (or a larger PlanShape)")
        if host.max_degree() > st.max_degree:
            raise ValueError(
                f"graph max degree {host.max_degree()} exceeds the plan "
                f"bound {st.max_degree}; compile a plan with a larger "
                "PlanShape.max_degree (the color tables would drop forbids)")
        return host, perm

    def _prepare(self, g) -> Tuple[object, Optional[np.ndarray]]:
        """Host graph -> (program input, relabel perm or None): the
        ``plan.canonicalize`` and ``plan.layout`` phases. Device strategies
        get the canonical DeviceGraph with its arrays still on the host:
        padded to the bucket, every static field pinned to the plan
        envelope so the jit cache key is constant across served graphs.
        Host strategies get the constraint Graph."""
        with spans.span("plan.canonicalize"):
            host, perm = self._canonicalize(g)
        if self.strategy.wants == "host":
            return host, perm
        st = self.statics
        with spans.span("plan.layout"):
            layout = ("edges", "ell") if self.backend.needs_ell else "edges"
            dg = host.host_layout(layout=layout,
                                  pad_edges_to=st.padded_edges,
                                  ell_width=max(1, st.max_degree))
        # num_directed_edges and max_degree are pytree aux data (= jit cache
        # key), and the impls read them only to size color tables, for which
        # the envelope bound is exactly as correct as the per-graph value
        return dataclasses.replace(dg, num_directed_edges=st.padded_edges,
                                   max_degree=st.max_degree), perm

    def _finish(self, raw: RawColoring, perm: Optional[np.ndarray],
                t0: float, *, batch_denom: int = 1) -> ColoringReport:
        return _build_report(raw, self.spec, self.strategy.name, perm, t0,
                             batch_denom=batch_denom)

    # ----------------------------------------------------------- introspection
    def wire_cost(self) -> Optional[dict]:
        """The closed-form bytes-on-wire cost table for this plan's
        envelope (distributed plans only; ``None`` otherwise).

        The same per-tier accounting the SPMD verifier checks the traced
        mesh program against (``repro.analysis.wirecost``) and the
        ``dist_scale`` benchmark asserts its measured bytes against —
        ``{"tiers": {"halo": {...}, "setup": {...}, ...}, ...}`` keyed by
        the plan's resolved wire, with the formula strings alongside the
        numbers."""
        if self.strategy.wants != "host":
            return None
        from ..analysis.wirecost import wire_cost_table
        return wire_cost_table(self.spec, self.statics)

    def lower(self, g, **runtime):
        """The plan's jitted program lowered for graph ``g`` (plus any
        per-call state), as a ``jax.stages.Lowered``: ``.compile()
        .as_text()`` is the HLO the device runs — e.g. whether a Pallas
        engine's kernel compiled to a ``tpu_custom_call``. Device
        strategies only (host strategies run several programs)."""
        if self.strategy.wants == "host" or self._degenerate:
            raise NotImplementedError(
                f"plan.lower needs a compiled device program; strategy "
                f"{self.strategy.name!r} with this envelope has none")
        canon, _ = self._prepare(g)
        state = self.strategy.plan_state(self.spec, self.statics, **runtime)
        return self._executor.lower(canon, *state)

    # ------------------------------------------------------------ execution
    def __call__(self, g, **runtime) -> ColoringReport:
        """Color ``g`` through the compiled program. ``runtime`` kwargs are
        per-call state for strategies that take it (``"recolor"``:
        ``colors=``, ``seed=``); stateless strategies reject any.

        Each call leaves one ``plan.call`` record in
        :mod:`repro.core.spans`; the report's ``wall_time_s`` is that
        span's duration."""
        with spans.call("plan.call") as root:
            t0 = root.start_ns * 1e-9
            canon, perm = self._prepare(g)
            state = self.strategy.plan_state(self.spec, self.statics,
                                             **runtime)
            if self._degenerate:  # validated above; nothing to run — but a
                # recolor warm start keeps its committed colors (the
                # strategy contract: non-seed vertices never change)
                report = _trivial_report(self.spec,
                                         self.statics.num_vertices, t0,
                                         colors=runtime.get("colors"))
            else:
                if self.strategy.wants == "device":
                    with spans.span("plan.transfer"):
                        canon, state = jax.block_until_ready(
                            jax.device_put((canon, state)))
                with spans.span("plan.device"):
                    raw = jax.block_until_ready(self._executor(canon, *state))
                with spans.span("plan.report"):
                    report = self._finish(raw, perm, t0)
            spans.count("wavefront_spills", report.wavefront_spills)
        report.wall_time_s = root.duration_ns * 1e-9
        return report

    def map(self, graphs: Sequence) -> list:
        """Color a batch of same-bucket graphs through ONE vmapped program.

        Returns one :class:`ColoringReport` per graph (original vertex ids,
        per-graph histories; ``wall_time_s`` is the batch time amortized
        per graph)."""
        if not self.strategy.supports_map:
            raise NotImplementedError(
                f"strategy {self.strategy.name!r} does not support batched "
                "plan.map execution")
        graphs = list(graphs)
        if not graphs:
            return []
        t0 = time.perf_counter()
        canons, perms = zip(*(self._prepare(g) for g in graphs))
        if self._degenerate:
            return [_trivial_report(self.spec, self.statics.num_vertices,
                                    t0, batch_denom=len(graphs))
                    for _ in graphs]
        if self._batched is None:
            self._batched = self.strategy.compile_batched(
                self.spec, self.statics, self._count_trace)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *canons)
        raws = self._batched(stacked)
        return [
            self._finish(jax.tree.map(lambda x, i=i: x[i], raws), perms[i],
                         t0, batch_denom=len(graphs))
            for i in range(len(graphs))
        ]


def compile_plan(spec: ColoringSpec, graph_or_shape,
                 verify: Optional[str] = None) -> ColoringPlan:
    """Compile ``spec`` against a graph (or an explicit :class:`PlanShape`)
    into a reusable :class:`ColoringPlan`.

    When given a graph, the envelope is derived from its *constraint* form:
    vertex count exact, directed-edge capacity rounded up to the
    :func:`repro.core.graph.pad_bucket` grid, max-degree bound taken as-is.
    Any later graph matching the envelope is served with zero retrace; pass
    a hand-built ``PlanShape`` to leave headroom for a whole family.

    ``verify`` runs the :mod:`repro.analysis` static analyzer over the
    plan's program and envelope before returning (DESIGN.md §Analysis):
    ``"warn"`` emits a Python warning for any finding not covered by the
    committed baseline, ``"error"`` raises
    :class:`repro.analysis.AnalysisError` instead. Distributed plans also
    run the SPMD verifier (collective safety, wire-cost model, halo
    exactness) over the traced mesh program. The analysis happens after
    construction but before the first trace, so a hazardous spec is
    reported (or refused) before any program runs."""
    plan = ColoringPlan(spec, graph_or_shape)
    if verify is not None:
        from ..analysis import verify_plan  # deferred: analysis optional
        verify_plan(plan.spec, plan.statics, mode=verify)
    return plan


# --------------------------------------------------------------------------
# one-shot front door
# --------------------------------------------------------------------------
def color(g, spec: Optional[ColoringSpec] = None, **overrides) -> ColoringReport:
    """One-shot front door: ``color(graph, spec)`` or
    ``color(graph, strategy="dataflow", model="d2", ...)``.

    Resolves the spec against the strategy/backend registries, applies the
    ordering (relabel in, un-relabel out — the report is in original vertex
    ids), runs the strategy exactly as its legacy driver would, and returns
    a :class:`ColoringReport`."""
    spec = ColoringSpec() if spec is None else spec
    if overrides:
        spec = dataclasses.replace(spec, **overrides)
    strategy = get_strategy(spec.strategy)
    if spec.ordering not in ORDERINGS:
        raise ValueError(f"unknown ordering {spec.ordering!r}; "
                         f"choose from {sorted(ORDERINGS)}")
    t0 = time.perf_counter()
    num_colored, num_edges = _graph_extent(g, spec)
    if num_colored == 0 or num_edges == 0:
        # degenerate input: nothing constrains anything — color 1
        # everywhere is valid under every model, and no engine program
        # needs to run (the distributed lowering cannot even express V=0)
        return _trivial_report(spec, num_colored, t0)
    perm = None
    if spec.ordering != "natural":
        if isinstance(g, DeviceGraph):
            raise ValueError(
                "ordering != 'natural' relabels on host: pass a Graph/"
                "BipartiteGraph (or pre-apply repro.core.ordering.apply)")
        host = constraint_host_graph(g, spec.model, side=spec.side)
        perm = _invert_order(ORDERINGS[spec.ordering](host,
                                                      spec.ordering_seed))
        # the constraint graph IS the d1 encoding of the model
        raw = strategy.oneshot(dataclasses.replace(spec, model="d1"),
                               host.relabel(perm))
    else:
        raw = strategy.oneshot(spec, g)
    return _build_report(raw, spec, strategy.name, perm, t0)
