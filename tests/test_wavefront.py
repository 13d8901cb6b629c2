"""The lockstep wavefront (repro.core.iterative.lockstep_wavefront): phase 1
of a full round computed class by class in offset order gives the chaotic
sweep's colors, conflicts and rounds bit for bit, across graph families,
concurrencies, engines, cold and warm starts, and forced spills; it engages
only where the static class count ceil(|V|/P) allows."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ColoringSpec, PlanShape, color, compile_plan, rmat,
                        spans)
from repro.core.engine import get_backend
from repro.core.frontier import resolve_frontier
from repro.core.graph import pad_bucket
from repro.core.iterative import (WAVEFRONT_EDGES_PER_CLASS, _iterative_impl,
                                  wavefront_capacities)

FAMILIES = ["RMAT-G", "RMAT-ER"]
ENGINES = ["sort", "bitmap", "ell_pallas", "fused_pallas"]
CONCURRENCY = [8, 64, 256]


def _device_graph(family, engine, scale=10, seed=3):
    g = rmat.paper_graph(family, scale=scale, seed=seed)
    layout = ("edges", "ell") if get_backend(engine).needs_ell else "edges"
    return g, g.to_device(layout=layout)


def _run(dg, engine, concurrency, *, wavefront_cap_e=-1, frontier=True,
         colors0=None, pending0=None, max_sweeps=4096):
    """``_iterative_impl`` as the iterative/recolor strategies call it;
    ``wavefront_cap_e=0`` keeps full rounds on the chaotic sweep."""
    fcv, fce = resolve_frontier(
        "auto" if frontier else "off", 0, num_vertices=dg.num_vertices,
        padded_edges=dg.padded_edges, max_degree=dg.max_degree,
        has_inc=dg.has_frontier)
    out = _iterative_impl(
        dg, colors0, pending0, concurrency=concurrency, max_rounds=256,
        max_sweeps=max_sweeps, backend=get_backend(engine),
        frontier_cap_v=fcv, frontier_cap_e=fce,
        seed_frontier=colors0 is not None, wavefront_cap_e=wavefront_cap_e)
    colors, rounds, conf, sweeps, fronts, left, spills = (
        np.asarray(x) for x in out)
    assert not left
    r = int(rounds)
    return dict(colors=colors, rounds=r, conflicts=conf[:r],
                sweeps=sweeps[:r], fronts=fronts[:r], spills=int(spills))


def _assert_same_coloring(chaotic, wave):
    np.testing.assert_array_equal(chaotic["colors"], wave["colors"])
    assert chaotic["rounds"] == wave["rounds"]
    np.testing.assert_array_equal(chaotic["conflicts"], wave["conflicts"])


def _assert_wavefront_passes(run):
    """A full round records one pass, and a round with a spilled class
    one more per chaotic sweep that finished it (at least the sweep that
    finds nothing to change); frontier rounds keep their chaotic sweep
    counts."""
    full = run["fronts"] == 0
    assert (run["sweeps"][full] >= 1).all()
    finished = int((run["sweeps"][full] > 1).sum())
    assert finished <= run["spills"]
    assert (finished == 0) == (run["spills"] == 0)


@pytest.mark.parametrize("concurrency", CONCURRENCY)
@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("family", FAMILIES)
def test_wavefront_matches_chaotic_cold_start(family, engine, concurrency):
    _, dg = _device_graph(family, engine)
    assert wavefront_capacities(
        dg.num_vertices, dg.padded_edges, dg.max_degree,
        concurrency=concurrency, max_sweeps=4096)[1] > 0
    chaotic = _run(dg, engine, concurrency, wavefront_cap_e=0)
    wave = _run(dg, engine, concurrency)
    _assert_same_coloring(chaotic, wave)
    np.testing.assert_array_equal(chaotic["fronts"], wave["fronts"])
    _assert_wavefront_passes(wave)
    # round 0 takes the full path: the wavefront, where chaos needs more
    assert wave["fronts"][0] == 0 and chaotic["sweeps"][0] > 1


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("family", FAMILIES)
def test_wavefront_matches_chaotic_every_full_round(family, engine):
    """With the frontier off every round is a full round, so every round
    runs the wavefront, from ever smaller pending sets."""
    _, dg = _device_graph(family, engine, scale=11, seed=5)
    chaotic = _run(dg, engine, 64, wavefront_cap_e=0, frontier=False)
    wave = _run(dg, engine, 64, frontier=False)
    assert wave["rounds"] > 2
    _assert_same_coloring(chaotic, wave)
    _assert_wavefront_passes(wave)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("family", FAMILIES)
def test_wavefront_matches_chaotic_recolor_warm_start(family, engine):
    """A recolor warm start: committed colors from a cold coloring, a
    third of the vertices seeded for repair (too many for the frontier
    slab, so round 0 runs the wavefront over the seed set)."""
    _, dg = _device_graph(family, engine, scale=11, seed=7)
    cold = _run(dg, engine, 64)
    V = dg.num_vertices
    seed = np.random.default_rng(0).random(V) < 0.33
    colors0 = np.where(seed, 0, cold["colors"]).astype(np.int32)
    chaotic = _run(dg, engine, 64, wavefront_cap_e=0, colors0=colors0,
                   pending0=seed)
    wave = _run(dg, engine, 64, colors0=colors0, pending0=seed)
    assert wave["fronts"][0] == 0, "the seed must take the full path"
    _assert_same_coloring(chaotic, wave)
    _assert_wavefront_passes(wave)
    # vertices outside the seed keep their committed colors
    np.testing.assert_array_equal(wave["colors"][~seed], colors0[~seed])


@pytest.mark.parametrize("engine", ENGINES)
def test_forced_spill_stays_identical_and_is_counted(engine):
    """A window far below a class's forbidding edges makes classes spill:
    the round finishes with chaotic full-edge sweeps, the same answer,
    counted."""
    _, dg = _device_graph("RMAT-G", engine)
    chaotic = _run(dg, engine, 64, wavefront_cap_e=0)
    spilled = _run(dg, engine, 64, wavefront_cap_e=16)
    _assert_same_coloring(chaotic, spilled)
    assert spilled["spills"] > 0
    _assert_wavefront_passes(spilled)


@pytest.mark.parametrize("cap_e", [-1, 16])
def test_batched_wavefront_matches_one_graph_at_a_time(cap_e):
    """Under vmap (the ``plan.map`` program) the spill test is batched:
    every graph of the batch still gets its own answer, with and without
    spills, and only spilled rounds add sweeps."""
    gs = [rmat.paper_graph("RMAT-G", scale=9, seed=s) for s in range(3)]
    shape = PlanShape(
        num_vertices=gs[0].num_vertices,
        padded_edges=pad_bucket(max(g.num_directed_edges for g in gs)),
        max_degree=max(g.max_degree() for g in gs))
    plan = compile_plan(ColoringSpec(strategy="iterative", concurrency=64),
                        shape)
    dgs = [plan._prepare(g)[0] for g in gs]
    fcv, fce = resolve_frontier(
        "auto", 0, num_vertices=shape.num_vertices,
        padded_edges=shape.padded_edges, max_degree=shape.max_degree,
        has_inc=dgs[0].has_frontier)
    run = functools.partial(
        _iterative_impl, concurrency=64, max_rounds=256, max_sweeps=4096,
        backend=get_backend("sort"), frontier_cap_v=fcv,
        frontier_cap_e=fce, wavefront_cap_e=cap_e)
    batched = jax.vmap(run)(jax.tree.map(lambda *xs: jnp.stack(xs), *dgs))
    for i, dg in enumerate(dgs):
        one = run(dg)
        for a, b in zip(one, batched):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[i])
        assert (int(one[-1]) > 0) == (cap_e > 0)


def test_engagement_rule_keeps_chaos_for_many_classes():
    """Fewer than WAVEFRONT_EDGES_PER_CLASS envelope edges per class
    ceil(|V|/P), or more classes than max_sweeps, keeps full rounds on the
    chaotic sweep; the envelope alone decides."""
    _, dg = _device_graph("RMAT-ER", "sort")
    V, E, D = dg.num_vertices, dg.padded_edges, dg.max_degree
    engaged = [p for p in range(1, V + 1) if wavefront_capacities(
        V, E, D, concurrency=p, max_sweeps=4096)[1]]
    P = min(engaged) - 1
    assert P >= 1 and -(-V // P) * WAVEFRONT_EDGES_PER_CLASS > E
    assert -(-V // (P + 1)) * WAVEFRONT_EDGES_PER_CLASS <= E
    assert wavefront_capacities(V, E, D, concurrency=P,
                                max_sweeps=4096) == (0, 0)
    assert wavefront_capacities(V, E, D, concurrency=64,
                                max_sweeps=V // 64 - 1) == (0, 0)
    cap_v, cap_e = wavefront_capacities(V, E, D, concurrency=64,
                                        max_sweeps=V // 64)
    assert cap_v >= 64 and 0 < cap_e <= E
    many = _run(dg, "sort", P)
    np.testing.assert_array_equal(
        many["sweeps"], _run(dg, "sort", P, wavefront_cap_e=0)["sweeps"])
    assert many["sweeps"][0] > 1 and many["spills"] == 0


def test_report_and_plan_call_carry_the_spill_count():
    """``ColoringReport.wavefront_spills`` and the ``plan.call`` record's
    counter of the same name agree; a full round records one pass."""
    g = rmat.paper_graph("RMAT-G", scale=10, seed=1)
    spec = ColoringSpec(strategy="iterative", concurrency=64,
                        max_rounds=256)
    plan = compile_plan(spec, g)
    rep = plan(g)
    assert rep.wavefront_spills == 0
    assert rep.sweeps_per_round[0] == 1
    assert spans.recent_calls(1)[0].counters == {"wavefront_spills": 0}
    one = color(g, spec)
    np.testing.assert_array_equal(one.colors, rep.colors)
    assert one.wavefront_spills == 0
