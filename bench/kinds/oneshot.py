"""The ``oneshot`` loop: whole colorings through
``compile_plan(spec, envelope)`` -> ``plan(g)``, one client, back to back.
It takes no parameters beyond its ``kind``."""
from __future__ import annotations

import traceback
from typing import Dict, List

import numpy as np

from bench import faults, rmat
from bench.loops import Phases, Record, closed_loop, envelope, spec_of
from bench.reference import EdgeSet, judge_coloring, palette


class Loop:
    """One plan compiled for the family, as a solver that colors many
    graphs of one kind keeps it.

    The graphs are the configuration's pool (``graph_seeds``). The window
    colors whole passes over the pool, every graph once a pass, in an
    order the run's seed picks, so every seed, and every program however
    fast, times the same graphs in the same proportions. Every call gets a
    fresh ``Graph`` made from copies of the pool graph's arrays, so no
    cache kept on a graph object can stand in for the work."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed

    def setup(self) -> None:
        from repro.core import compile_plan
        c = self.config
        clock = Phases()
        self.V = 1 << int(c["scale"])
        self.pool = [rmat.generate(s, scale=c["scale"],
                                   edge_factor=c["edge_factor"],
                                   rmat=c["rmat"], shuffle=c["shuffle"])
                     for s in c["graph_seeds"]]
        self.order = rmat.rng_for(self.seed, rmat.ORDER_STREAM).permutation(
            len(self.pool)).tolist()
        clock.mark("generate")
        self.described = "graphs (seed: directed edges, max degree): " + \
            ", ".join(f"{s}: {p.graph.num_directed_edges}, "
                      f"{p.graph.max_degree()}"
                      for s, p in zip(c["graph_seeds"], self.pool)) + \
            f"; order {self.order}"
        self.plan = compile_plan(spec_of(c), envelope(c))
        self.color = self.plan
        clock.mark("compile_plan")
        # one program serves every graph of the pool
        self.color(self.pool[0].graph)
        clock.mark("warm_call")
        self.phases = clock.phases
        self.traces0 = self.plan.traces
        self.calls: list = []   # (pool index, report or None)
        self.errors: List[str] = []

    def window(self, seconds: float, annotate) -> Record:
        def step(i):
            k = self.order[i % len(self.order)]
            g = self.pool[k].graph
            g = type(g)(g.num_vertices, g.row_ptr.copy(), g.col_idx.copy())
            with annotate("plan_call"):
                try:
                    self.calls.append((k, self.color(g)))
                except Exception:  # a coloring that failed is counted
                    self.calls.append((k, None))
                    self.errors.append(traceback.format_exc())

        n, elapsed = closed_loop(step, seconds, per_pass=len(self.pool))
        rec = Record(self.config, self.V, attempted=n, window_s=elapsed)
        rec.colorings = [dict(
            num_directed_edges=self.pool[k].graph.num_directed_edges,
            rounds=int(r.rounds),
            sweeps_per_round=np.asarray(r.sweeps_per_round).tolist(),
            conflicts_per_round=np.asarray(r.conflicts_per_round).tolist(),
            frontier_sizes_per_round=np.asarray(
                r.frontier_sizes_per_round).tolist())
            for k, r in self.calls if r is not None]
        rec.calls = [(k, r.wall_time_s) for k, r in self.calls
                     if r is not None]
        rec.end_to_end["color_s"] = elapsed / n
        rec.retraces = self.plan.traces - self.traces0
        return rec

    def check(self, rec: Record) -> Dict[str, tuple]:
        refs: Dict[int, EdgeSet] = {}
        worst = {"conflict_edges": 0, "uncolored": 0, "max_color": 0}
        bound = 0
        failed = 0
        for k, r in self.calls:
            if k not in refs:
                refs[k] = EdgeSet.from_pairs(self.V, self.pool[k].pairs)
            limit = refs[k].max_degree() + 1
            bound = max(bound, limit)
            if r is None:
                failed += 1
                continue
            j = judge_coloring(refs[k], r.colors)
            if j["conflict_edges"] or j["uncolored"] or \
                    not 0 < j["max_color"] <= limit:
                failed += 1
            for key in worst:
                worst[key] = max(worst[key], j[key])
        rec.failed = failed
        colors = [palette(r.colors) for _, r in self.calls if r is not None]
        rec.end_to_end["colors"] = max(colors) if colors else None
        return {"conflict_edges": (worst["conflict_edges"], 0),
                "uncolored": (worst["uncolored"], 0),
                "max_color": (worst["max_color"], bound),
                "wrong_answers": (failed, 0)}

    def plant(self, fault: str) -> None:
        """Break the coloring call with ``fault`` (``faults.FAULTS`` or
        ``faults.CONTROL``)."""
        plan = self.plan
        if fault == "unchanged_state":
            self.color = lambda g: faults.replace_colors(
                plan(g), np.zeros(g.num_vertices, np.int32))
        elif fault == "half_batch":
            def half(g):
                rep = plan(g)
                c = np.array(rep.colors, copy=True)
                c[: c.size // 2] = 0
                return faults.replace_colors(rep, c)
            self.color = half
        elif fault == "altered_answer":
            def altered(g):
                rep = plan(g)
                return faults.replace_colors(rep, faults.alter(g, rep.colors))
            self.color = altered
        elif fault == faults.CONTROL:
            self.color = faults.speculation_only(plan)
        else:
            raise KeyError(fault)
