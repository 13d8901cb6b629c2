"""repro.analysis: seeded-violation fixtures, budget/bind guards, baseline
semantics, and the clean-run pin.

Each violation class the analyzer exists to catch is *seeded* here as a
minimal program (a mutation-style fixture) and asserted to produce its
exact finding code — so a refactor that silently blinds a pass turns a
test red, not just the lint lane. The flip side is pinned too: the
shipping registry plus the committed baseline must verify clean
(``compile_plan(..., verify="error")`` is a no-op on every shipping plan).
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (AnalysisConfig, AnalysisError, Finding,
                            analyze_spec, compare, dedupe, gating,
                            lint_tree, load_baseline, save_baseline,
                            split_by_severity, sweep_registry,
                            verify_findings, verify_plan)
from repro.analysis import budgets, deadcode, races, retrace
from repro.analysis.__main__ import main as analysis_main
from repro.core.api import ColoringSpec, PlanShape, compile_plan
from repro.core.engine import get_backend

sds = jax.ShapeDtypeStruct
SHAPE = PlanShape(num_vertices=48, padded_edges=512, max_degree=8)


def codes(findings):
    return [f.code for f in findings]


# --------------------------------------------------------------------------
# race classifier: one seeded jaxpr per class, exact code asserted
# --------------------------------------------------------------------------
def _scatter_codes(fn, *avals):
    return codes(races.classify_scatters(jax.make_jaxpr(fn)(*avals)))


class TestRaceClassifier:
    def test_float_accumulate_is_race201(self):
        got = _scatter_codes(lambda x, i, u: x.at[i].add(u),
                             sds((16,), jnp.float32), sds((4,), jnp.int32),
                             sds((4,), jnp.float32))
        assert got == ["RACE201"]

    def test_int_accumulate_is_race202(self):
        got = _scatter_codes(lambda x, i, u: x.at[i].add(u),
                             sds((16,), jnp.int32), sds((4,), jnp.int32),
                             sds((4,), jnp.int32))
        assert got == ["RACE202"]

    def test_commutative_reduction_is_race101(self):
        got = _scatter_codes(lambda x, i, u: x.at[i].min(u),
                             sds((16,), jnp.int32), sds((4,), jnp.int32),
                             sds((4,), jnp.int32))
        assert got == ["RACE101"]

    def test_static_iota_indices_are_race102(self):
        got = _scatter_codes(lambda x, u: x.at[jnp.arange(4)].set(u),
                             sds((16,), jnp.int32), sds((4,), jnp.int32))
        assert got == ["RACE102"]

    def test_single_update_row_is_race104(self):
        got = _scatter_codes(lambda x, i, u: x.at[i].set(u),
                             sds((16,), jnp.int32), sds((), jnp.int32),
                             sds((), jnp.int32))
        assert got == ["RACE104"]

    def test_idempotent_constant_store_is_race103(self):
        got = _scatter_codes(lambda x, i: x.at[i].set(1),
                             sds((16,), jnp.int32), sds((4,), jnp.int32))
        assert got == ["RACE103"]

    def test_unique_indices_assertion_is_race301(self):
        got = _scatter_codes(
            lambda x, i, u: x.at[i].set(u, unique_indices=True),
            sds((16,), jnp.int32), sds((4,), jnp.int32), sds((4,), jnp.int32))
        assert got == ["RACE301"]

    def test_speculative_lww_store_is_race300(self):
        # the paper's deliberately-racy store shape: data-driven indices,
        # data updates, no uniqueness claim — benign only via Alg. 2 phase 2
        got = _scatter_codes(lambda x, i, u: x.at[i].set(u),
                             sds((16,), jnp.int32), sds((4,), jnp.int32),
                             sds((4,), jnp.int32))
        assert got == ["RACE300"]

    def test_info_classes_never_gate(self):
        fs = [Finding("RACE101", "a:b", "m"), Finding("RACE104", "a:c", "m")]
        assert gating(fs) == []


# --------------------------------------------------------------------------
# retrace-hazard lint: AST pass + trace-constant pass
# --------------------------------------------------------------------------
_SRC_NONE_DEFAULT = """
import functools, jax
@functools.partial(jax.jit, static_argnames=("interpret",))
def f(x, interpret=None):
    return x
"""

_SRC_IS_NONE_BODY = """
import jax
@jax.jit(static_argnames=("mode",))
def g(x, mode="fast"):
    if mode is None:
        mode = "fast"
    return x
"""

_SRC_MUTABLE_DEFAULT = """
import functools, jax
@functools.partial(jax.jit, static_argnames=("opts",))
def h(x, opts=[]):
    return x
"""

_SRC_SANCTIONED = """
import functools, jax
@functools.partial(jax.jit, static_argnames=("interpret",))
def k(x, interpret=False):
    return x
"""


class TestRetraceLint:
    def test_none_default_static_arg_is_retrace001(self):
        got = retrace.lint_source(_SRC_NONE_DEFAULT, "fixture.py")
        assert codes(got) == ["RETRACE001"]
        assert "interpret" in got[0].message

    def test_is_none_test_in_body_is_retrace001(self):
        got = retrace.lint_source(_SRC_IS_NONE_BODY, "fixture.py")
        assert codes(got) == ["RETRACE001"]

    def test_mutable_default_is_retrace002(self):
        got = retrace.lint_source(_SRC_MUTABLE_DEFAULT, "fixture.py")
        assert codes(got) == ["RETRACE002"]

    def test_resolved_outside_jit_is_clean(self):
        assert retrace.lint_source(_SRC_SANCTIONED, "fixture.py") == []

    def test_closure_captured_data_is_retrace003(self):
        data = jnp.asarray(np.arange(256, dtype=np.int32) ** 2)
        closed = jax.make_jaxpr(lambda x: x + data)(sds((256,), jnp.int32))
        assert codes(retrace.check_trace_constants(closed)) == ["RETRACE003"]

    @pytest.mark.parametrize("const", [
        jnp.arange(256, dtype=jnp.int32),       # iota ramp
        jnp.full((256,), 7, jnp.int32),         # constant fill
        jnp.asarray(np.arange(8) ** 2),         # below the size threshold
    ], ids=["ramp", "fill", "small"])
    def test_envelope_derived_constants_exempt(self, const):
        closed = jax.make_jaxpr(lambda x: x + const)(
            sds(const.shape, const.dtype))
        assert retrace.check_trace_constants(closed) == []


# --------------------------------------------------------------------------
# budget checker: bit fields, int32 indexing, VMEM model
# --------------------------------------------------------------------------
class TestBudgets:
    def test_color_bound_past_bit28_is_bit001(self):
        got = budgets.check_spec_budgets(
            ColoringSpec(engine="sort", color_bound=1 << 28), SHAPE)
        assert codes(got) == ["BIT001"]
        assert got[0].severity == "error"

    def test_max_color_bound_is_accepted(self):
        got = budgets.check_spec_budgets(
            ColoringSpec(engine="sort", color_bound=(1 << 28) - 1), SHAPE)
        assert "BIT001" not in codes(got)

    def test_huge_max_degree_is_bit001(self):
        got = budgets.check_spec_budgets(
            ColoringSpec(engine="sort"), PlanShape(8, 512, 1 << 28))
        assert "BIT001" in codes(got)

    def test_ell_slab_overflow_is_idx001(self):
        got = budgets.check_spec_budgets(
            ColoringSpec(engine="ell_pallas"),
            PlanShape(2 ** 20, 1 << 20, 2 ** 12))
        assert "IDX001" in codes(got)

    def test_edge_capacity_overflow_is_idx002(self):
        got = budgets.check_spec_budgets(
            ColoringSpec(engine="sort"), PlanShape(48, 2 ** 31, 8))
        assert codes(got) == ["IDX002"]

    def test_high_degree_breaches_declared_vmem(self):
        # max_degree 32768 -> 1025 forbidden-bitset words -> the fused
        # kernel's own closed-form model (a [W, 8, 512] int32 bitset) lands
        # ~17 MB, over the 16 MiB default ceiling, with no tracing involved
        got = budgets.check_spec_budgets(
            ColoringSpec(engine="fused_pallas"),
            PlanShape(512, 1 << 16, 1 << 15))
        assert codes(got) == ["VMEM001"]
        assert got[0].site == "kernels/round_fused.py:round_fused"

    def test_default_shape_fits_default_ceiling(self):
        for eng in ("ell_pallas", "fused_pallas"):
            got = budgets.check_spec_budgets(ColoringSpec(engine=eng), SHAPE)
            assert got == [], eng

    def test_traced_pallas_geometry_respects_ceiling_knob(self):
        # same plan, ceiling squeezed to 1 KiB: the traced pallas_call
        # geometry (real block shapes + scratch) must now breach
        fs = analyze_spec(ColoringSpec(strategy="iterative",
                                       engine="fused_pallas"), SHAPE,
                          config=AnalysisConfig(vmem_ceiling_bytes=1024))
        assert "VMEM001" in codes(fs)


class TestBindGuard:
    """Satellite: table backends reject a bound the packed entry cannot
    encode at bind time, not at first corrupt coloring."""

    def test_bitmap_bind_rejects_29bit_bound(self):
        with pytest.raises(ValueError, match="packed-entry color field"):
            get_backend("bitmap").bind(num_vertices=8, max_colors=1 << 28)

    def test_ell_bind_rejects_29bit_bound(self):
        with pytest.raises(ValueError, match="packed-entry color field"):
            get_backend("ell_pallas").bind(
                num_vertices=8, max_colors=1 << 28,
                ell_slot=jnp.zeros((16,), jnp.int32), ell_width=4,
                max_degree=3)

    def test_bind_accepts_the_field_maximum(self):
        get_backend("bitmap").bind(num_vertices=8,
                                   max_colors=(1 << 28) - 1)


# --------------------------------------------------------------------------
# findings / dedupe / baseline plumbing
# --------------------------------------------------------------------------
class TestFindings:
    def test_unregistered_code_rejected(self):
        with pytest.raises(ValueError, match="unregistered"):
            Finding("RACE999", "a:b", "m")

    def test_severity_defaults_from_registry(self):
        assert Finding("RACE300", "a:b", "m").severity == "warning"
        assert Finding("BIT001", "a:b", "m").severity == "error"
        assert Finding("RACE104", "a:b", "m").severity == "info"

    def test_fingerprint_excludes_context_and_message(self):
        a = Finding("RACE300", "core/x.py:f", "m1", "iterative/sort/d1")
        b = Finding("RACE300", "core/x.py:f", "m2", "dataflow/bitmap/d2")
        assert a.fingerprint == b.fingerprint == "RACE300@core/x.py:f"

    def test_dedupe_folds_contexts(self):
        a = Finding("RACE300", "core/x.py:f", "m", "ctx1")
        b = Finding("RACE300", "core/x.py:f", "m", "ctx2")
        c = Finding("RACE301", "core/y.py:g", "m", "ctx1")
        out = dedupe([a, b, c])
        assert len(out) == 2
        assert out[0].context == "ctx1 +1 more"
        assert out[1].context == "ctx1"

    def test_split_by_severity(self):
        fs = [Finding("BIT001", "a:b", "m"), Finding("RACE300", "a:c", "m"),
              Finding("RACE104", "a:d", "m")]
        errs, warns, infos = split_by_severity(fs)
        assert (codes(errs), codes(warns), codes(infos)) == (
            ["BIT001"], ["RACE300"], ["RACE104"])


class TestBaseline:
    def test_round_trip(self, tmp_path):
        path = str(tmp_path / "b.json")
        save_baseline({"RACE300@core/x.py:f": "distinct by construction"},
                      path)
        assert load_baseline(path) == {
            "RACE300@core/x.py:f": "distinct by construction"}

    def test_empty_reason_rejected(self, tmp_path):
        path = str(tmp_path / "b.json")
        path_doc = {"version": 1, "entries": [
            {"fingerprint": "RACE300@core/x.py:f", "reason": "  "}]}
        with open(path, "w") as f:
            json.dump(path_doc, f)
        with pytest.raises(ValueError, match="no reason string"):
            load_baseline(path)

    def test_version_mismatch_rejected(self, tmp_path):
        path = str(tmp_path / "b.json")
        with open(path, "w") as f:
            json.dump({"version": 99, "entries": []}, f)
        with pytest.raises(ValueError, match="unsupported version"):
            load_baseline(path)

    def test_compare_three_outcomes(self):
        fs = [Finding("RACE300", "core/x.py:f", "m"),       # allowlisted
              Finding("BIT001", "core/y.py:g", "m"),        # new
              Finding("RACE104", "core/z.py:h", "m")]       # info: ignored
        base = {"RACE300@core/x.py:f": "ok",
                "RACE301@core/gone.py:f": "stale entry"}
        new, allowed, stale = compare(fs, base)
        assert codes(new) == ["BIT001"]
        assert codes(allowed) == ["RACE300"]
        assert stale == ["RACE301@core/gone.py:f"]

    def test_committed_baseline_loads_with_reasons(self):
        base = load_baseline()
        assert base, "committed baseline must not be empty"
        for fp, reason in base.items():
            assert "@" in fp and reason.strip()


# --------------------------------------------------------------------------
# dead-export scan
# --------------------------------------------------------------------------
def _mini_repo(tmp_path, module_source, extra=None):
    """A throwaway repo layout: src/pkg/<mod>.py (+ optional extra files)."""
    pkg = tmp_path / "src" / "pkg"
    pkg.mkdir(parents=True)
    (pkg / "mod.py").write_text(module_source)
    for rel, text in (extra or {}).items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    return str(pkg), str(tmp_path)


class TestDeadCode:
    def test_unreferenced_export_is_dead001(self, tmp_path):
        pkg, repo = _mini_repo(tmp_path, "def orphan_helper():\n    pass\n")
        got = deadcode.scan_package(pkg, repo)
        assert codes(got) == ["DEAD001"]
        assert got[0].site.endswith("mod.py:orphan_helper")

    def test_cross_file_reference_counts(self, tmp_path):
        pkg, repo = _mini_repo(
            tmp_path, "def live_helper():\n    pass\n",
            extra={"tests/test_mod.py":
                   "def test_it():\n    live_helper()\n"})
        assert deadcode.scan_package(pkg, repo) == []

    def test_reexport_plumbing_does_not_count(self, tmp_path):
        # an import statement elsewhere is NOT a reference (laundering rule)
        pkg, repo = _mini_repo(
            tmp_path, "def laundered():\n    pass\n",
            extra={"src/pkg/other.py": "from .mod import laundered\n"})
        assert codes(deadcode.scan_package(pkg, repo)) == ["DEAD001"]

    def test_pending_pragma_downgrades_to_dead100(self, tmp_path):
        pkg, repo = _mini_repo(
            tmp_path,
            "# pending: wire-up later\ndef dormant():\n    pass\n")
        got = deadcode.scan_package(pkg, repo)
        assert codes(got) == ["DEAD100"]
        assert got[0].severity == "info"
        assert "dormant" in got[0].message
        assert "wire-up later" in got[0].message

    def test_compression_wired_up_pragma_gone(self):
        # parallel/compression.py used to carry a "# pending: dist_scale
        # wire-up" pragma (DEAD100 downgrade); the boundary wire now
        # consumes its halo codec, so the pragma is gone and the repo-wide
        # scan must stay free of DEAD001 without it — every export is live.
        path = os.path.join(os.path.dirname(deadcode.__file__),
                            "..", "parallel", "compression.py")
        with open(path) as f:
            assert deadcode.PENDING_PRAGMA.search(f.read()) is None
        got = [f for f in lint_tree() if f.code.startswith("DEAD")]
        assert [f for f in got if f.code == "DEAD001"] == []


# --------------------------------------------------------------------------
# verify= front door + the clean-run pin
# --------------------------------------------------------------------------
class TestVerify:
    def test_seeded_bit001_raises_under_error(self):
        with pytest.raises(AnalysisError, match="BIT001"):
            verify_plan(ColoringSpec(engine="sort", color_bound=1 << 28),
                        SHAPE, mode="error")

    def test_seeded_bit001_warns_under_warn(self):
        with pytest.warns(UserWarning, match="BIT001"):
            verify_plan(ColoringSpec(engine="sort", color_bound=1 << 28),
                        SHAPE, mode="warn")

    def test_compile_plan_verify_error_rejects_seeded_violation(self):
        with pytest.raises(AnalysisError, match="BIT001"):
            compile_plan(ColoringSpec(engine="sort", color_bound=1 << 28),
                         SHAPE, verify="error")

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError, match="warn"):
            verify_plan(ColoringSpec(), SHAPE, mode="loud")

    def test_verify_findings_reports_stale(self, tmp_path):
        path = str(tmp_path / "b.json")
        save_baseline({"RACE300@core/gone.py:f": "code was deleted"}, path)
        new, allowed, stale = verify_findings(
            [], mode="warn", config=AnalysisConfig(baseline_path=path))
        assert (new, allowed) == ([], [])
        assert stale == ["RACE300@core/gone.py:f"]

    def test_shipping_plan_verifies_clean(self):
        # the acceptance pin: verify="error" is a no-op on a shipping plan
        plan = compile_plan(ColoringSpec(), SHAPE, verify="error")
        assert plan.statics == SHAPE

    @pytest.mark.parametrize("strategy,engine", [
        ("dataflow", "bitmap"), ("recolor", "fused_pallas")])
    def test_more_shipping_combos_verify_clean(self, strategy, engine):
        verify_plan(ColoringSpec(strategy=strategy, engine=engine), SHAPE,
                    mode="error")

    def test_source_tree_gating_findings_all_race_allowlisted(self):
        # the source passes (AST lint + dead exports) must contribute zero
        # gating findings of their own — the baseline holds only the race
        # benignity arguments
        assert gating(lint_tree()) == []


@pytest.mark.slow
class TestFullSweep:
    def test_registry_sweeps_clean_against_committed_baseline(self):
        from repro.analysis import sweep_distributed
        # the full lint lane: registry + distributed wire/scheme sweep
        findings = dedupe(sweep_registry() + sweep_distributed()
                          + lint_tree())
        baseline = load_baseline()
        new, allowed, stale = compare(findings, baseline)
        assert [f.format() for f in new] == []
        assert stale == []
        # every entry in the committed baseline is exercised
        assert {f.fingerprint for f in allowed} == set(baseline)
        # no combination fell back to ANALYSIS000 (unverified != clean)
        assert "ANALYSIS000" not in codes(findings)


# --------------------------------------------------------------------------
# CLI (the lint-lane entry point)
# --------------------------------------------------------------------------
class TestCli:
    def test_single_cell_sweep_is_clean_and_dumps_json(self, tmp_path):
        # a partial sweep exercises only a subset of the committed baseline,
        # so the lane's stale-entry rule would (correctly) trip; scope the
        # baseline to exactly this cell's gating fingerprints instead
        cell = dict(strategies=("iterative",), engines=("sort",),
                    models=("d1",))
        fps = {f.fingerprint: "scoped to the iterative/sort/d1 cell"
               for f in gating(sweep_registry(**cell))}
        assert fps, "the iterative/sort cell must have gating findings"
        base = str(tmp_path / "cell.json")
        save_baseline(fps, base)
        out = str(tmp_path / "findings.json")
        rc = analysis_main(["--strategies", "iterative", "--engines", "sort",
                            "--models", "d1", "--no-source",
                            "--baseline", base, "--json", out])
        assert rc == 0
        with open(out) as f:
            doc = json.load(f)
        assert doc["findings"] and all(d["code"] and d["site"]
                                       for d in doc["findings"])
        assert doc["summary"]["new"] == 0 and doc["summary"]["stale"] == 0
        assert doc["wire_cost"] == []  # populated only under --distributed

    def test_stale_baseline_exits_2(self, tmp_path):
        # drift-only (no new violations) is its own stable exit code so CI
        # can distinguish "fix your code" from "prune the baseline"
        cell = dict(strategies=("iterative",), engines=("sort",),
                    models=("d1",))
        fps = {f.fingerprint: "scoped" for f in gating(sweep_registry(**cell))}
        fps["RACE300@core/nowhere.py:f"] = "stale"
        base = str(tmp_path / "b.json")
        save_baseline(fps, base)
        rc = analysis_main(["--strategies", "iterative", "--engines", "sort",
                            "--models", "d1", "--no-source",
                            "--baseline", base])
        assert rc == 2

    def test_new_violation_exits_1(self, tmp_path):
        # an unbaselined gating finding dominates: exit 1 even when stale
        # entries are also present
        base = str(tmp_path / "b.json")
        save_baseline({"RACE300@core/nowhere.py:f": "stale"}, base)
        rc = analysis_main(["--strategies", "iterative", "--engines", "sort",
                            "--models", "d1", "--no-source",
                            "--baseline", base])
        assert rc == 1

    def test_distributed_flag_sweeps_clean_with_wire_cost(self, tmp_path):
        from repro.analysis import sweep_distributed
        fps = {f.fingerprint: "scoped to the distributed/sort cells"
               for f in gating(dedupe(
                   sweep_registry(strategies=("distributed",),
                                  engines=("sort",), models=("d1",))
                   + sweep_distributed(engines=("sort",))))}
        base = str(tmp_path / "cell.json")
        save_baseline(fps, base)
        out = str(tmp_path / "report.json")
        rc = analysis_main(["--strategies", "distributed",
                            "--engines", "sort", "--models", "d1",
                            "--distributed", "--no-source",
                            "--baseline", base, "--json", out])
        assert rc == 0
        with open(out) as f:
            doc = json.load(f)
        # one closed-form cost table per wire x scheme cell, each carrying
        # the tier accounting the dist_scale benchmark asserts against
        assert len(doc["wire_cost"]) == 6
        for t in doc["wire_cost"]:
            tiers = t["tiers"]
            if t["wire"] == "boundary":
                assert {"halo", "setup"} <= set(tiers)
            else:
                assert "spill" in tiers
        spmd = {d["code"] for d in doc["findings"]
                if d["code"].startswith(("COLL", "WIRE", "HALO"))}
        assert {"COLL101", "COLL102", "WIRE101", "HALO101"} <= spmd
