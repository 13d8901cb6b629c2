"""repro.analysis — static analyzer for compiled coloring plans.

Walks the jaxpr of every compiled :class:`~repro.core.api.ColoringPlan`
program (including Pallas kernel bodies) plus the package source, and
reports typed :class:`~repro.analysis.findings.Finding` values across
three passes:

* **race classifier** (:mod:`.races`) — every scatter/store, classified
  against the paper's benign-speculation model;
* **retrace-hazard lint** (:mod:`.retrace`) — trace-time static-arg
  sentinels, non-hashable statics, plan-envelope constant leaks;
* **budget checker** (:mod:`.budgets`) — packed-entry bit fields, int32
  index arithmetic, per-BlockSpec VMEM footprints.

Distributed (host-strategy) plans additionally run the SPMD verifier over
the traced mesh program:

* **collective safety** (:mod:`.collectives`) — branch-parity and
  shard-uniformity proofs for every collective under control flow;
* **wire-cost model** (:mod:`.wirecost`) — traced bytes-on-wire checked
  against the closed-form tier accounting (DESIGN.md §Perf);
* **halo exactness** (:mod:`.halo`) — dataflow proof that only
  boundary/slab selections cross the wire and raw payloads are read only
  through the ``[Vp]`` snapshot patch.

Three front doors:

* ``compile_plan(spec, shape, verify="warn"|"error")`` — per-plan gate
  (:func:`verify_plan` under the hood);
* ``python -m repro.analysis`` — full registry sweep against the
  committed baseline (:mod:`.__main__`);
* ``tools/lint_plans.py`` — the CI lane: sweep + source lint + dead-code
  scan + baseline-drift check.

Severity / baseline semantics live in :mod:`.findings` and
:mod:`.baseline`; DESIGN.md §Analysis is the narrative version.
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Iterable, List, Optional, Sequence, Tuple

from .findings import (CODES, AnalysisError, Finding, dedupe, gating,
                       split_by_severity)
from .baseline import (compare, default_baseline_path, load_baseline,
                       save_baseline)
from . import budgets as _budgets
from . import collectives as _collectives
from . import deadcode as _deadcode
from . import halo as _halo
from . import races as _races
from . import retrace as _retrace
from . import wirecost as _wirecost
from .spmd import SpmdGeometry, distributed_geometry
from .collectives import check_collectives
from .halo import check_halo_exactness
from .wirecost import check_wire_cost, closed_form_table, wire_cost_table

__all__ = [
    "AnalysisConfig", "AnalysisError", "Finding", "CODES",
    "analyze_plan", "analyze_spec", "lint_tree", "sweep_registry",
    "sweep_distributed", "verify_findings", "verify_plan", "dedupe",
    "gating", "split_by_severity", "compare", "load_baseline",
    "save_baseline", "default_baseline_path", "SpmdGeometry",
    "distributed_geometry", "check_collectives", "check_wire_cost",
    "check_halo_exactness", "closed_form_table", "wire_cost_table",
]

# the registry axes a sweep covers by default (every shipping combination)
SWEEP_STRATEGIES = ("iterative", "dataflow", "distributed", "recolor")
SWEEP_ENGINES = ("sort", "bitmap", "ell_pallas", "fused_pallas")
SWEEP_MODELS = ("d1", "d2", "pd2")
# the distributed-sweep axes (--distributed): every wire x partition cell
SWEEP_WIRES = ("boundary", "full", "auto")
SWEEP_SCHEMES = ("1d", "2d")


@dataclasses.dataclass(frozen=True)
class AnalysisConfig:
    """Knobs shared by every front door.

    vmem_ceiling_bytes  per-grid-step Pallas VMEM budget (None = 16 MiB,
                        or the REPRO_ANALYSIS_VMEM_CEILING env var);
    baseline_path       allowlist location (None = the committed
                        ``repro/analysis/baseline.json``).
    """

    vmem_ceiling_bytes: Optional[int] = None
    baseline_path: Optional[str] = None


def _abstract_device_graph(statics, backend):
    """A :class:`~repro.core.graph.DeviceGraph` of ``ShapeDtypeStruct``
    leaves matching the plan envelope — enough to ``jax.make_jaxpr`` the
    plan program without any concrete graph. ``inc_ptr`` is always present
    so the frontier execution path (where the interesting scatters live)
    is part of the traced program."""
    import jax
    import jax.numpy as jnp
    from ..core.graph import DeviceGraph

    V = int(statics.num_vertices)
    E = int(statics.padded_edges)
    D = max(1, int(statics.max_degree))
    sds = jax.ShapeDtypeStruct
    return DeviceGraph(
        num_vertices=V, num_directed_edges=E,
        src=sds((E,), jnp.int32), dst=sds((E,), jnp.int32),
        max_degree=int(statics.max_degree),
        ell_slot=sds((E,), jnp.int32) if backend.needs_ell else None,
        ell_width=backend.layout_width(D) if backend.needs_ell else 0,
        inc_ptr=sds((V + 1,), jnp.int32))


def trace_plan_program(spec, statics):
    """``ClosedJaxpr`` of the program a plan with this spec/envelope would
    compile — device strategies via their ``device_program`` over an
    abstract DeviceGraph, the distributed host strategy via its slab-shaped
    mesh program (mirroring ``DistributedStrategy.compile``)."""
    import jax
    import jax.numpy as jnp
    from ..core.api import get_strategy
    from ..core.engine import get_backend

    strategy = get_strategy(spec.strategy)
    backend = get_backend(spec.engine)
    V = int(statics.num_vertices)
    sds = jax.ShapeDtypeStruct

    if strategy.wants == "host":
        from jax import set_mesh
        # one geometry derivation shared with the SPMD passes' closed-form
        # expectations (spmd.distributed_geometry), so the traced program
        # and the accounting can never disagree about the envelope. The
        # boundary program is traced with a non-empty halo slab even when
        # the envelope carries none (the sweep mesh is 1 device, where Bl
        # is always 0): the wire code is shape-generic, and the classifier
        # must see the scatters a real multi-device plan compiles. Floor 2,
        # not 1 — a width-1 slab is a single update row, which the race
        # classifier would (correctly for THAT shape, wrongly for the
        # fleet's) discharge as unable to self-collide
        g = distributed_geometry(spec, statics)
        mesh = strategy._mesh(spec)
        fn = strategy._build(spec, mesh, verts_local=g.verts_local,
                             edges_local=g.edges_local,
                             max_colors=g.max_colors,
                             ell_width=int(statics.max_degree),
                             wire=g.wire, wire_colors=g.wire_colors)
        shaped = sds((g.num_devices, g.edges_local), jnp.int32)
        bshaped = sds((g.num_devices, max(1, g.boundary_cap)), jnp.int32)
        with set_mesh(mesh):
            return jax.make_jaxpr(fn)(shaped, shaped, bshaped)

    prog = strategy.device_program(spec, backend)
    dg = _abstract_device_graph(statics, backend)
    if spec.strategy == "recolor":
        return jax.make_jaxpr(prog)(dg, sds((V,), jnp.int32),
                                    sds((V,), jnp.bool_))
    return jax.make_jaxpr(prog)(dg)


def analyze_spec(spec, statics, *, config: Optional[AnalysisConfig] = None,
                 context: Optional[str] = None) -> List[Finding]:
    """All plan-scoped passes for one spec/envelope: spec-level budgets,
    then trace the program and run the race classifier, the envelope-leak
    check, and the traced-geometry VMEM audit. Distributed (host) plans
    additionally run the SPMD verifier: collective safety, the static
    wire-cost model, and the halo-exactness proof. An untraceable
    combination yields ANALYSIS000 (the cell is *unverified*, not
    clean)."""
    from ..core.api import _plan_shape, get_strategy
    from ..core.engine import get_backend

    config = config or AnalysisConfig()
    statics = _plan_shape(spec, statics)
    ctx = context if context is not None else \
        f"{spec.strategy}/{spec.engine if isinstance(spec.engine, str) else get_backend(spec.engine).name}/{spec.model}"
    findings = _budgets.check_spec_budgets(
        spec, statics, vmem_ceiling=config.vmem_ceiling_bytes, context=ctx)
    if statics.num_vertices == 0 or statics.padded_edges == 0:
        return findings  # degenerate envelope: no program exists to trace
    try:
        closed = trace_plan_program(spec, statics)
    except Exception as e:  # noqa: BLE001 — any trace failure is a finding
        findings.append(Finding(
            "ANALYSIS000", f"plan:{spec.strategy}",
            f"program could not be traced: {type(e).__name__}: {e}", ctx))
        return findings
    findings += _races.classify_scatters(closed, context=ctx)
    findings += _retrace.check_trace_constants(
        closed, context=ctx, site=f"plan:{spec.strategy}")
    findings += _budgets.check_pallas_vmem(
        closed, vmem_ceiling=config.vmem_ceiling_bytes, context=ctx)
    if get_strategy(spec.strategy).wants == "host":
        g = distributed_geometry(spec, statics)
        findings += _collectives.check_collectives(closed, context=ctx)
        findings += _wirecost.check_wire_cost(closed, g, context=ctx)
        findings += _halo.check_halo_exactness(closed, g, context=ctx)
    return findings


def analyze_plan(plan, *, config: Optional[AnalysisConfig] = None
                 ) -> List[Finding]:
    """:func:`analyze_spec` over an already-compiled plan's envelope."""
    return analyze_spec(plan.spec, plan.statics, config=config)


def _package_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_tree(package_root: Optional[str] = None,
              repo_root: Optional[str] = None) -> List[Finding]:
    """Source-level passes (no tracing): the retrace AST lint over every
    module in the package, plus the dead-export scan."""
    pkg = package_root or _package_root()
    repo = repo_root or os.path.dirname(os.path.dirname(pkg))
    findings = _retrace.lint_package(pkg)
    findings += _deadcode.scan_package(pkg, repo)
    return findings


def sweep_registry(statics=None, *,
                   strategies: Sequence[str] = SWEEP_STRATEGIES,
                   engines: Sequence[str] = SWEEP_ENGINES,
                   models: Sequence[str] = SWEEP_MODELS,
                   config: Optional[AnalysisConfig] = None,
                   progress=None) -> List[Finding]:
    """Analyze every strategy x engine x model combination, deduped by
    fingerprint (a site shared by many plans folds to one finding).

    Plan programs operate on the *constraint* graph, so the model axis
    only changes the host-side lowering — the traced program for
    (strategy, engine) is model-independent and traced once; the model
    axis still runs the (cheap) spec-budget pass per combination."""
    from ..core.api import ColoringSpec, PlanShape

    config = config or AnalysisConfig()
    statics = statics or PlanShape(num_vertices=48, padded_edges=512,
                                   max_degree=8)
    findings: List[Finding] = []
    for strat in strategies:
        for eng in engines:
            for i, model in enumerate(models):
                spec = ColoringSpec(strategy=strat, engine=eng, model=model)
                ctx = f"{strat}/{eng}/{model}"
                if progress is not None:
                    progress(ctx)
                if i == 0:
                    findings += analyze_spec(spec, statics, config=config,
                                             context=ctx)
                else:
                    findings += _budgets.check_spec_budgets(
                        spec, statics,
                        vmem_ceiling=config.vmem_ceiling_bytes, context=ctx)
    return dedupe(findings)


def sweep_distributed(statics=None, *,
                      wires: Sequence[str] = SWEEP_WIRES,
                      schemes: Sequence[str] = SWEEP_SCHEMES,
                      engines: Sequence[str] = SWEEP_ENGINES,
                      config: Optional[AnalysisConfig] = None,
                      progress=None) -> List[Finding]:
    """The distributed sweep (``--distributed``): every wire x partition
    scheme x engine cell of the host strategy, deduped by fingerprint.

    The partition scheme only changes host-side graph partitioning and
    ``wire="auto"`` traces the same boundary program as
    ``wire="boundary"`` — so the mesh program is traced once per
    (engine, resolved-wire) pair; the remaining cells still run the
    (cheap) spec-budget pass so every combination is covered."""
    from ..core.api import ColoringSpec, PlanShape

    config = config or AnalysisConfig()
    statics = statics or PlanShape(num_vertices=48, padded_edges=512,
                                   max_degree=8)
    findings: List[Finding] = []
    traced = set()
    for wire in wires:
        for scheme in schemes:
            for eng in engines:
                spec = ColoringSpec(strategy="distributed", engine=eng,
                                    wire=wire, partition=scheme)
                ctx = f"distributed/{eng}/wire={wire}/{scheme}"
                if progress is not None:
                    progress(ctx)
                cell = (eng, "full" if wire == "full" else "boundary")
                if cell in traced:
                    findings += _budgets.check_spec_budgets(
                        spec, statics,
                        vmem_ceiling=config.vmem_ceiling_bytes, context=ctx)
                else:
                    traced.add(cell)
                    findings += analyze_spec(spec, statics, config=config,
                                             context=ctx)
    return dedupe(findings)


def verify_findings(findings: Iterable[Finding], *, mode: str = "warn",
                    config: Optional[AnalysisConfig] = None
                    ) -> Tuple[List[Finding], List[Finding], List[str]]:
    """Compare findings against the committed baseline and enforce ``mode``:
    ``"warn"`` emits a Python warning per new violation, ``"error"``
    raises :class:`AnalysisError`. Returns (new, allowlisted, stale)."""
    if mode not in ("warn", "error"):
        raise ValueError(f'verify mode must be "warn" or "error", '
                         f'got {mode!r}')
    config = config or AnalysisConfig()
    baseline = load_baseline(config.baseline_path)
    new, allowed, stale = compare(findings, baseline)
    if new:
        text = "\n".join(f.format() for f in new)
        if mode == "error":
            raise AnalysisError(
                f"{len(new)} non-allowlisted finding(s):\n{text}")
        warnings.warn(f"repro.analysis: {len(new)} non-allowlisted "
                      f"finding(s):\n{text}", stacklevel=3)
    return new, allowed, stale


def verify_plan(spec, statics, *, mode: str = "warn",
                config: Optional[AnalysisConfig] = None) -> List[Finding]:
    """The ``compile_plan(..., verify=...)`` gate: analyze one plan's
    spec/envelope and enforce the baseline. Returns the (deduped) findings
    when it does not raise."""
    findings = dedupe(analyze_spec(spec, statics, config=config))
    verify_findings(findings, mode=mode, config=config)
    return findings
