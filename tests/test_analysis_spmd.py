"""SPMD verifier fixtures: every COLL/WIRE/HALO code seeded as a minimal
mesh program (or a mutation of the shipping one) and asserted to produce
its exact finding code, plus clean-run pins on all shipping wire tiers.

The toy fixtures build one-device ``shard_map`` programs by hand so each
pass sees exactly one structural feature; the mutation fixtures
monkeypatch the real distributed driver so a *plausible* refactor (an
extra collective in one cond branch, a widened wire codec) is caught by
``compile_plan(verify="error")`` before anything compiles.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis import (AnalysisError, SpmdGeometry, analyze_spec,
                            check_collectives, check_halo_exactness,
                            check_wire_cost, verify_plan)
from repro.core.api import ColoringSpec, PlanShape, compile_plan
from jax import shard_map

sds = jax.ShapeDtypeStruct
SHAPE = PlanShape(num_vertices=48, padded_edges=512, max_degree=8)


def codes(findings):
    return [f.code for f in findings]


def mesh_jaxpr(fn, *avals, n_in=None):
    """Trace ``fn`` through a one-device shard_map (every aval sharded
    over the single "x" axis)."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    n = len(avals) if n_in is None else n_in
    sm = shard_map(fn, mesh=mesh, in_specs=(P("x"),) * n, out_specs=P("x"))
    return jax.make_jaxpr(sm)(*avals)


def toy_geometry(**kw):
    base = dict(num_devices=1, verts_local=8, edges_local=64,
                boundary_cap=2, wire="boundary", wire_colors=9,
                max_colors=9, frontier_cap_v=0, frontier_cap_e=0,
                axis_names=("x",))
    base.update(kw)
    return SpmdGeometry(**base)


X8 = sds((8,), jnp.int32)


def _varying(v):
    """Tag ``v`` device-varying over "x": a psum result (or a constant)
    must carry the same varying-axes type as the branch or loop value it
    meets."""
    return lax.pcast(v, "x", to="varying")


# --------------------------------------------------------------------------
# collective safety: one toy program per COLL code
# --------------------------------------------------------------------------
class TestCollectives:
    def test_branch_mismatch_under_varying_pred_is_coll201(self):
        def fn(x):
            return lax.cond(x[0] > 0,                 # shard-varying
                            lambda v: _varying(lax.psum(v, "x")),
                            lambda v: v * 2, x)
        got = codes(check_collectives(mesh_jaxpr(fn, X8)))
        assert "COLL201" in got and "COLL103" not in got

    def test_identical_branch_sequences_is_coll103(self):
        def fn(x):
            return lax.cond(x[0] > 0,
                            lambda v: lax.psum(v, "x") + 1,
                            lambda v: lax.psum(v, "x") * 2, x)
        got = codes(check_collectives(mesh_jaxpr(fn, X8)))
        assert "COLL103" in got and "COLL201" not in got

    def test_psum_derived_uniform_pred_is_coll102(self):
        def fn(x):
            total = lax.psum(x.sum(), "x")            # replicated vote
            return lax.cond(total > 0,
                            lambda v: _varying(lax.psum(v, "x")),
                            lambda v: v * 2, x)
        got = codes(check_collectives(mesh_jaxpr(fn, X8)))
        assert "COLL102" in got
        assert not {"COLL103", "COLL201"} & set(got)

    def test_varying_loop_exit_with_collectives_is_coll202(self):
        def fn(x):
            def body(c):
                v, i = c
                return lax.psum(v, "x") * 0 + v, i + 1
            v, _ = lax.while_loop(lambda c: c[0][0] > 0, body,
                                  (x, jnp.int32(0)))
            return v
        got = codes(check_collectives(mesh_jaxpr(fn, X8)))
        assert "COLL202" in got

    def test_uniform_loop_exit_is_not_coll202(self):
        def fn(x):
            def body(c):
                v, i = c
                return lax.psum(v, "x") * 0 + v, i + 1
            v, _ = lax.while_loop(lambda c: c[1] < 3, body,
                                  (x, jnp.int32(0)))
            return v
        assert "COLL202" not in codes(check_collectives(mesh_jaxpr(fn, X8)))

    def test_unread_exchange_patched_carrier_is_coll203(self):
        def fn(x):
            def body(c):
                s, i = c
                g = lax.all_gather(s[:2], "x", tiled=True)
                return jnp.concatenate([g, s[2:]]), i + 1   # never read
            s, _ = lax.while_loop(lambda c: c[1] < 3, body,
                                  (x, jnp.int32(0)))
            return s
        assert "COLL203" in codes(check_collectives(mesh_jaxpr(fn, X8)))

    def test_in_round_read_clears_coll203(self):
        def fn(x):
            def body(c):
                s, i = c
                g = lax.all_gather(s[:2], "x", tiled=True)
                s2 = jnp.concatenate([g, s[2:]])
                return s2, i + s2[0] * 0                    # read in-round
            s, _ = lax.while_loop(lambda c: c[1] < 3, body,
                                  (x, _varying(jnp.int32(0))))
            return s
        assert "COLL203" not in codes(check_collectives(mesh_jaxpr(fn, X8)))


# --------------------------------------------------------------------------
# wire cost: a tiny exchange program per WIRE code (geometry: D=1, Vl=8,
# Bl=2, C=9 -> halo = 1 packed word = 4B/round, setup = 8B)
# --------------------------------------------------------------------------
def _wire_prog(round_width=1, setup_width=2, extra_gather=False,
               wide_psum=False):
    def fn(s, bids):
        setup = lax.all_gather(bids[:setup_width], "x", tiled=True)

        def body(c):
            v, i = c
            w = lax.all_gather(v[:round_width], "x", tiled=True)
            v = v + w.sum() + setup.sum() * 0
            if extra_gather:
                v = v + lax.all_gather(v[:1], "x", tiled=True).sum()
            if wide_psum:
                v = v + lax.psum(v[:4], "x").sum()
            vote = lax.psum(i, "x")                   # scalar control plane
            return v, i + vote * 0 + 1
        v, _ = lax.while_loop(lambda c: c[1] < 2, body, (s, jnp.int32(0)))
        return v
    return fn


class TestWireCost:
    def test_exact_tiers_are_wire101_only(self):
        got = codes(check_wire_cost(
            mesh_jaxpr(_wire_prog(), X8, X8), toy_geometry()))
        assert got == ["WIRE101"]

    def test_widened_round_payload_is_wire201(self):
        got = codes(check_wire_cost(
            mesh_jaxpr(_wire_prog(round_width=2), X8, X8), toy_geometry()))
        assert "WIRE201" in got

    def test_extra_round_gather_is_wire202(self):
        got = codes(check_wire_cost(
            mesh_jaxpr(_wire_prog(extra_gather=True), X8, X8),
            toy_geometry()))
        assert "WIRE202" in got and "WIRE201" not in got

    def test_nonscalar_psum_is_wire202(self):
        got = codes(check_wire_cost(
            mesh_jaxpr(_wire_prog(wide_psum=True), X8, X8), toy_geometry()))
        assert "WIRE202" in got

    def test_oversized_setup_exchange_is_wire203(self):
        got = codes(check_wire_cost(
            mesh_jaxpr(_wire_prog(setup_width=4), X8, X8), toy_geometry()))
        assert "WIRE203" in got


# --------------------------------------------------------------------------
# halo exactness: payload-width and read-side sinks (Vl = Vp = 8, D = 1)
# --------------------------------------------------------------------------
def _round_loop(body_fn):
    def fn(x):
        s, _ = lax.while_loop(lambda c: c[1] < 2, body_fn,
                              (x, jnp.int32(0)))
        return s
    return fn


class TestHaloExactness:
    def test_full_local_state_on_wire_is_halo201(self):
        def body(c):
            s, i = c
            g = lax.all_gather(s, "x", tiled=True)    # 8 entries >= Vl
            return s + g[:8] * 0, i + 1
        got = codes(check_halo_exactness(
            mesh_jaxpr(_round_loop(body), X8), toy_geometry()))
        assert got == ["HALO201"]

    def test_raw_payload_into_conflict_compare_is_halo202(self):
        def body(c):
            s, i = c
            g = lax.all_gather(s[:2], "x", tiled=True)
            conflict = (g == s[:2]).sum()             # raw payload compared
            return s + conflict, i + 1
        got = codes(check_halo_exactness(
            mesh_jaxpr(_round_loop(body), X8), toy_geometry()))
        assert got == ["HALO202"]

    def test_raw_payload_into_foreign_table_is_halo202(self):
        def body(c):
            s, i = c
            g = lax.all_gather(s[:2], "x", tiled=True)
            tbl = jnp.zeros((4,), jnp.int32)          # not the [Vp] view
            tbl = tbl.at[g % 4].set(1, mode="drop")
            return s + tbl.sum(), i + 1
        got = codes(check_halo_exactness(
            mesh_jaxpr(_round_loop(body), X8), toy_geometry()))
        assert "HALO202" in got

    def test_patch_through_vp_snapshot_proves_halo101(self):
        def body(c):
            s, i = c
            g = lax.all_gather(s[:2], "x", tiled=True)
            snap = s.at[jnp.arange(2)].set(g, mode="drop")  # the [Vp] patch
            conflict = (snap[:2] == s[:2]).sum()      # patched view only
            return snap + conflict * 0, i + 1
        got = codes(check_halo_exactness(
            mesh_jaxpr(_round_loop(body), X8), toy_geometry()))
        assert got == ["HALO101"]

    def test_full_wire_is_exempt(self):
        def body(c):
            s, i = c
            g = lax.all_gather(s, "x", tiled=True)
            return s + g[:8] * 0, i + 1
        got = check_halo_exactness(
            mesh_jaxpr(_round_loop(body), X8),
            toy_geometry(wire="full", boundary_cap=0))
        assert got == []


# --------------------------------------------------------------------------
# mutation fixtures on the SHIPPING program: a plausible refactor must be
# caught by compile_plan(verify="error") before anything compiles
# --------------------------------------------------------------------------
class TestShippingMutations:
    def test_branch_local_collective_rejected_as_coll201(self, monkeypatch):
        # seed the issue's acceptance mutation: a psum inside slab_solve
        # only — the solve cond's branches then issue mismatched collective
        # sequences under the shard-varying fits_solve predicate
        import repro.core.distributed as dist
        real = dist.frontier_sweep

        def mutant(*args, **kw):
            out = real(*args, **kw)
            leaf = jax.tree_util.tree_leaves(out)[0]
            vote = lax.psum(jnp.ravel(leaf)[0].astype(jnp.int32), "x")
            return jax.tree_util.tree_map(
                lambda a: jnp.where(vote < 0, a, a), out)
        monkeypatch.setattr(dist, "frontier_sweep", mutant)
        with pytest.raises(AnalysisError, match="COLL201"):
            compile_plan(ColoringSpec(strategy="distributed"), SHAPE,
                         verify="error")

    def test_widened_wire_codec_rejected_as_wire201(self, monkeypatch):
        # widen the halo codec to one entry per word without updating the
        # documented closed form: traced bytes-on-wire drift -> WIRE201
        import repro.parallel.compression as comp
        monkeypatch.setattr(comp, "halo_bits", lambda bound: 32)
        spec = ColoringSpec(strategy="distributed", wire="boundary")
        got = codes(analyze_spec(spec, SHAPE))
        assert "WIRE201" in got
        with pytest.raises(AnalysisError, match="WIRE201"):
            verify_plan(spec, SHAPE, mode="error")


# --------------------------------------------------------------------------
# clean-run pins: every shipping wire tier verifies clean and carries the
# three info-grade proofs
# --------------------------------------------------------------------------
class TestShippingClean:
    @pytest.mark.parametrize("wire", ["boundary", "full", "auto"])
    def test_wire_tiers_verify_clean(self, wire):
        verify_plan(ColoringSpec(strategy="distributed", wire=wire), SHAPE,
                    mode="error")

    def test_partition_2d_verifies_clean(self):
        verify_plan(ColoringSpec(strategy="distributed", partition="2d"),
                    SHAPE, mode="error")

    def test_frontier_off_verifies_clean(self):
        verify_plan(ColoringSpec(strategy="distributed", frontier="off"),
                    SHAPE, mode="error")

    def test_boundary_plan_carries_all_three_proofs(self):
        got = codes(analyze_spec(
            ColoringSpec(strategy="distributed", wire="boundary"), SHAPE))
        # COLL102: wire-selection cond proven uniform; WIRE101: the cost
        # table; HALO101: the exactness proof
        assert {"COLL101", "COLL102", "WIRE101", "HALO101"} <= set(got)
        assert not any(c.startswith(("COLL2", "WIRE2", "HALO2"))
                       for c in got)

    def test_full_plan_skips_halo_and_prices_spill(self):
        got = codes(analyze_spec(
            ColoringSpec(strategy="distributed", wire="full"), SHAPE))
        assert "WIRE101" in got
        assert not any(c.startswith("HALO") for c in got)
