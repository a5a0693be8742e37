"""Edge-of-stability contracts for the queueing closed forms.

The paper's models are only meaningful strictly inside the stability region;
these tests pin the behaviour AT the boundary: waits blow up finitely and
monotonically as rho -> 1-, every path (scalar math, numpy-broadcast, jitted
vectorized) reports inf at rho >= 1 for permissive specs, and eager Scenario
validation raises ScenarioError naming the offending field identically
whether the spec is later consumed by the scalar or the vectorized engine.
"""

import math

import numpy as np
import pytest

from repro.core import latency as L
from repro.core import queueing as Q
from repro.core.latency import NetworkPath, ServiceModel, Tier, Workload
from repro.core.scenario import EdgeSpec, Scenario, ScenarioError
from repro.fleet import ScenarioBatch, fleet_analytic
from repro.fleet.analytic_vec import (
    md1_wait_vec,
    mg1_wait_vec,
    mm1_wait_vec,
    mmk_wait_erlang_vec,
)
from repro.jaxenv import x64

MU = 10.0
# rho ladder approaching 1 from below; float64 still resolves mu - lam here
RHOS = 1.0 - np.geomspace(1e-1, 1e-9, 17)


class TestBlowupFiniteAndMonotone:
    @pytest.mark.parametrize("wait", [Q.mm1_wait, Q.md1_wait,
                                      lambda lam, mu: Q.mg1_wait(lam, mu, 0.02)])
    def test_scalar_forms(self, wait):
        vals = [wait(rho * MU, MU) for rho in RHOS]
        assert all(math.isfinite(v) for v in vals), "rho < 1 must stay finite"
        assert all(b > a for a, b in zip(vals, vals[1:])), "blowup must be monotone"
        assert vals[-1] > 1e6  # genuinely blowing up, not saturating

    def test_numpy_broadcast_forms(self):
        lam = RHOS * MU
        for w in (L.mm1_wait(lam, MU), L.md1_wait(lam, MU),
                  L.mg1_wait(lam, MU, 0.02)):
            w = np.asarray(w)
            assert np.all(np.isfinite(w))
            assert np.all(np.diff(w) > 0)

    def test_vectorized_jax_forms(self):
        # the vec primitives are documented to run inside a scoped x64
        # context (fleet_analytic provides it); replicate that here
        lam = RHOS * MU
        with x64():
            waits = [np.asarray(w) for w in (
                mm1_wait_vec(lam, MU), md1_wait_vec(lam, MU),
                mg1_wait_vec(lam, MU, 0.02))]
        for w in waits:
            assert np.all(np.isfinite(w))
            assert np.all(np.diff(w) > 0)

    def test_erlang_c_exact_and_vectorized(self):
        k = 4
        lam = RHOS * k * MU
        exact = np.array([Q.mmk_wait_erlang(la, MU, k) for la in lam])
        vec = np.asarray(mmk_wait_erlang_vec(lam, MU, float(k)))
        assert np.all(np.isfinite(exact)) and np.all(np.diff(exact) > 0)
        np.testing.assert_allclose(vec, exact, rtol=1e-9)

    def test_scalar_and_vectorized_blowups_match_pointwise(self):
        lam = RHOS * MU
        scalar = np.array([Q.mm1_wait(la, MU) for la in lam])
        with x64():
            vec = np.asarray(mm1_wait_vec(lam, MU))
        np.testing.assert_allclose(vec, scalar, rtol=1e-12)


class TestAtAndPastSaturation:
    @pytest.mark.parametrize("rho", [1.0, 1.0 + 1e-12, 1.5, 10.0])
    def test_every_path_reports_inf(self, rho):
        lam = rho * MU
        assert Q.mm1_wait(lam, MU) == math.inf
        assert Q.md1_wait(lam, MU) == math.inf
        assert Q.mg1_wait(lam, MU, 0.02) == math.inf
        assert Q.mmk_wait_erlang(lam * 4, MU, 4) == math.inf  # lam >= k*mu
        assert np.asarray(L.mm1_wait(lam, MU)) == np.inf
        with x64():
            assert np.asarray(mm1_wait_vec(np.array([lam]), MU))[0] == np.inf
            assert np.asarray(md1_wait_vec(np.array([lam]), MU))[0] == np.inf
            assert np.asarray(mg1_wait_vec(np.array([lam]), MU, 0.02))[0] == np.inf

    def test_negative_arrival_is_inf_not_negative_wait(self):
        assert Q.mm1_wait(-1.0, MU) == math.inf
        with x64():
            assert np.asarray(mm1_wait_vec(np.array([-1.0]), MU))[0] == np.inf


def _spec(lam: float, *, allow_unstable: bool = False, **kw) -> Scenario:
    defaults = dict(
        workload=Workload(arrival_rate=lam, req_bytes=30_000, res_bytes=1_000),
        device=Tier("dev", 0.150),
        edges=(EdgeSpec(Tier("edge", 0.028)),),
        network=NetworkPath(2.5e6),
        allow_unstable=allow_unstable,
    )
    defaults.update(kw)
    return Scenario(**defaults)


class TestScenarioValidationConsistency:
    def test_device_saturation_raises_named_field(self):
        # device k*mu = 1/0.15 = 6.67: rho >= 1 must raise, not return inf
        with pytest.raises(ScenarioError) as ei:
            _spec(7.0)
        assert ei.value.field == "device"
        # just inside the boundary constructs fine
        _spec(6.6)

    def test_edge_saturation_raises_named_field(self):
        with pytest.raises(ScenarioError) as ei:
            _spec(40.0, device=Tier("dev", 0.01), network=NetworkPath(2.5e7))
        assert ei.value.field == "edges[0]"

    def test_nic_saturation_raises_named_field(self):
        with pytest.raises(ScenarioError) as ei:
            _spec(5.0, network=NetworkPath(30_000 * 4.0))  # lam >= B/D_req
        assert ei.value.field == "network.bandwidth_Bps"

    def test_scalar_and_vectorized_consume_the_same_validation(self):
        """rho >= 1 raises identically regardless of downstream engine: the
        vectorized packers take validated Scenarios, so the SAME ScenarioError
        fires before either path can run."""
        with pytest.raises(ScenarioError):
            ScenarioBatch.from_scenarios([_spec(7.0)])
        with pytest.raises(ScenarioError):
            ScenarioBatch.from_sweep(_spec(7.0), {"workload.arrival_rate": [1.0]})

    def test_allow_unstable_yields_inf_consistently_across_paths(self):
        """With allow_unstable=True both engines agree: inf exactly where the
        spec saturates, finite elsewhere — no NaNs, no negatives."""
        base = _spec(1.0, allow_unstable=True)
        lams = [1.0, 6.0, 6.67, 7.5, 40.0, 120.0]
        scns = base.sweep("workload.arrival_rate", lams)
        batch = ScenarioBatch.from_scenarios(scns)
        pred = fleet_analytic(batch)
        for i, scn in enumerate(scns):
            scalar = scn.analytic().totals()
            vec = pred.totals(i)
            for key, v in scalar.items():
                vv = vec[key]
                assert not (np.isnan(v) or np.isnan(vv)), (key, v, vv)
                if np.isinf(v):
                    assert np.isinf(vv), (key, v, vv)
                else:
                    assert v >= 0 and vv == pytest.approx(v, rel=1e-9)
        # the sweep genuinely crossed saturation on both paths
        assert np.isinf(pred.t_dev).any() and np.isfinite(pred.t_dev).any()

    def test_fractional_k_refused_by_both_simulators(self):
        scn = _spec(1.0, device=Tier("dev", 0.15, parallelism_k=1.5))
        with pytest.raises(ScenarioError, match="parallelism"):
            scn.simulate("on_device", n=100)
        from repro.fleet import simulate_fleet
        with pytest.raises(ValueError, match="fractional"):
            simulate_fleet(ScenarioBatch.from_scenarios([scn]), "on_device", n=100)


class TestServiceModelBoundary:
    def test_general_tier_with_zero_var_matches_deterministic(self):
        # GENERAL with Var[s]=0 must equal the M/D/1 prediction exactly
        det = _spec(3.0, device=Tier("d", 0.15)).analytic().totals()["on_device"]
        gen = _spec(3.0, device=Tier(
            "d", 0.15, service_model=ServiceModel.GENERAL, service_var=0.0,
        )).analytic().totals()["on_device"]
        assert gen == pytest.approx(det, rel=1e-12)

    def test_general_tier_with_exponential_var_matches_mm1(self):
        s = 0.15
        exp = _spec(3.0, device=Tier(
            "d", s, service_model=ServiceModel.EXPONENTIAL,
        )).analytic().totals()["on_device"]
        gen = _spec(3.0, device=Tier(
            "d", s, service_model=ServiceModel.GENERAL, service_var=s * s,
        )).analytic().totals()["on_device"]
        assert gen == pytest.approx(exp, rel=1e-12)
