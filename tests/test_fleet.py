"""Tests for the repro.fleet subsystem: vectorized-vs-scalar coherence,
batched simulation, trace generators, and the §5 adaptive-replay result."""

import numpy as np
import pytest

from _prop import given, settings, st

from repro.core import (
    EdgeSpec,
    NetworkPath,
    Scenario,
    ScenarioError,
    ServiceModel,
    Tier,
    Workload,
    analytic,
    crossovers,
    simulate,
)
from repro.core.multitenant import TenantStream
from repro.core.queueing import mmk_wait_erlang
from repro.core.simulation import station_pass
from repro.fleet import (
    ScenarioBatch,
    Trace,
    drift_signal,
    fleet_analytic,
    fleet_crossover,
    lindley_station,
    make_trace,
    mmk_wait_erlang_vec,
    mmpp_signal,
    replay,
    simulate_fleet,
    step_signal,
)

REL_TOL = 1e-9


def _assert_matches_scalar(pred, i, scn):
    tot = analytic(scn).totals()
    vec = pred.totals(i)
    for key, v in tot.items():
        vv = vec[key]
        if np.isinf(v):
            assert np.isinf(vv), (key, v, vv)
        else:
            assert abs(v - vv) <= REL_TOL * abs(v), (key, v, vv)
    assert pred.strategy_names()[i] == analytic(scn).best_strategy


def _paper_point(**kw) -> Scenario:
    defaults = dict(
        workload=Workload(2.0, 30_000, 1_000, name="inceptionv4"),
        device=Tier("tx2", 0.150),
        edges=(EdgeSpec(Tier("a2", 0.028)),),
        network=NetworkPath(5e6 / 8),
    )
    defaults.update(kw)
    return Scenario(**defaults)


# strategy space for property-style coherence: service model x rates x sizes
_models = st.sampled_from(list(ServiceModel))
_point = st.tuples(
    st.floats(0.1, 20.0),     # lam
    st.floats(0.005, 0.5),    # dev service s
    st.floats(0.002, 0.1),    # edge service s
    st.floats(1.0, 4.0),      # edge k
    st.floats(0.2, 50.0),     # bandwidth Mbps
    _models,                  # device model
    _models,                  # edge model
    st.integers(0, 2),        # background tenants
)


class TestBatchPacking:
    def test_from_scenarios_round_numbers(self):
        scn = _paper_point()
        batch = ScenarioBatch.from_scenarios([scn, scn])
        assert batch.size == len(batch) == 2
        assert batch.max_edges == 1
        assert np.all(batch.n_edges == 1)
        assert batch.lam[0] == 2.0 and batch.edge_s[0, 0] == 0.028
        assert np.isnan(batch.edge_bw[0, 0])  # unset override

    def test_edge_padding_and_no_edge_rows(self):
        two_edges = _paper_point(edges=(
            EdgeSpec(Tier("a", 0.03)), EdgeSpec(Tier("b", 0.02), bandwidth_Bps=1e6)))
        no_edges = _paper_point(edges=())
        batch = ScenarioBatch.from_scenarios([two_edges, no_edges])
        assert batch.max_edges == 2
        assert list(batch.n_edges) == [2, 0]
        pred = fleet_analytic(batch)
        assert np.all(np.isinf(pred.t_edge[1]))  # padding never wins
        assert pred.strategy_names()[1] == "on_device"
        _assert_matches_scalar(pred, 0, two_edges)

    def test_from_sweep_matches_grid_rows(self):
        base = _paper_point()
        axes = {
            "network.bandwidth_Bps": np.geomspace(2e5, 2e7, 3),
            "workload.arrival_rate": np.linspace(0.5, 6.0, 4),
        }
        grid = base.grid(axes)
        batch = ScenarioBatch.from_sweep(base, axes)
        assert batch.size == len(grid) == 12
        pred = fleet_analytic(batch)
        for i, scn in enumerate(grid):
            _assert_matches_scalar(pred, i, scn)

    def test_from_sweep_descending_axis_on_stable_base_matches_grid(self):
        # regression: the fail-fast probe must allow unstable values exactly
        # like grid()/sweep() do, regardless of axis value ORDER
        base = _paper_point()  # allow_unstable=False, device cap ~6.67 rps
        axes = {"workload.arrival_rate": np.linspace(30.0, 0.5, 4)}
        grid = base.grid(axes)
        batch = ScenarioBatch.from_sweep(base, axes)
        pred = fleet_analytic(batch)
        for i, scn in enumerate(grid):
            _assert_matches_scalar(pred, i, scn)

    def test_from_sweep_rejects_unknown_paths(self):
        base = _paper_point()
        with pytest.raises(ScenarioError):
            ScenarioBatch.from_sweep(base, {"device.name": [1.0]})
        with pytest.raises(ScenarioError):
            ScenarioBatch.from_sweep(base, {"edges[3].tier.service_time_s": [0.1]})

    def test_grid_row_order_contract_pinned_column_exact(self):
        """THE row-matching contract: packing ``base.grid(axes)`` row by row
        is COLUMN-IDENTICAL to ``from_sweep(base, axes)`` — same C order
        (last axis fastest), same values, bit-for-bit. Previously this was
        asserted only via latency agreement; pin the packed arrays directly
        so a silent reordering in either constructor fails loudly here."""
        base = _paper_point()
        axes = {
            "workload.arrival_rate": np.linspace(0.5, 6.0, 3),
            "edges[0].tier.service_time_s": np.array([0.01, 0.03]),
            "network.bandwidth_Bps": np.geomspace(2e5, 2e7, 4),
        }
        via_grid = ScenarioBatch.from_scenarios(base.grid(axes))
        via_sweep = ScenarioBatch.from_sweep(base, axes)
        assert via_grid.size == via_sweep.size == 3 * 2 * 4
        for name, col in via_grid.arrays().items():
            np.testing.assert_array_equal(
                col, via_sweep.arrays()[name], err_msg=name, strict=True)
        # and the C-order invariant itself: the LAST axis varies fastest
        bw = via_sweep.bandwidth_Bps
        assert np.array_equal(bw[:4], np.geomspace(2e5, 2e7, 4))
        assert np.array_equal(bw, np.tile(np.geomspace(2e5, 2e7, 4), 6))
        lam = via_sweep.lam
        assert np.array_equal(lam, np.repeat(np.linspace(0.5, 6.0, 3), 8))

    def test_from_sweep_rejects_invalid_later_values_like_grid(self):
        # regression: only the FIRST axis value used to be probed, so a zero
        # rate in position 2 was silently packed while grid() raised — the
        # two constructors must reject exactly the same axes
        base = _paper_point()
        for axes in (
            {"workload.arrival_rate": [5.0, 0.0]},
            {"workload.arrival_rate": [5.0, -1.0]},
            {"network.bandwidth_Bps": [1e6, float("nan")]},
            {"workload.res_bytes": [1000.0, -5.0]},
            {"edges[0].tier.service_time_s": [0.01, 0.0]},
        ):
            with pytest.raises(ScenarioError):
                base.grid(axes)
            with pytest.raises(ScenarioError):
                ScenarioBatch.from_sweep(base, axes)


class TestSweepErgonomics:
    def test_sweep_accepts_numpy_arrays_and_iterables(self):
        base = _paper_point()
        swept = base.sweep("workload.arrival_rate", np.linspace(1, 5, 3))
        assert [s.workload.arrival_rate for s in swept] == [1.0, 3.0, 5.0]
        # numpy scalars are coerced: the spec stays exactly JSON-round-trippable
        assert all(isinstance(s.workload.arrival_rate, float) for s in swept)
        assert all(Scenario.from_dict(s.to_dict()) == s for s in swept)
        gen = (x for x in (2.0, 4.0))
        assert len(base.sweep("workload.arrival_rate", gen)) == 2

    def test_grid_is_c_ordered(self):
        base = _paper_point()
        grid = base.grid({"workload.arrival_rate": [1.0, 2.0],
                          "network.bandwidth_Bps": [1e5, 1e6, 1e7]})
        assert len(grid) == 6
        # last axis fastest
        assert [s.workload.arrival_rate for s in grid[:3]] == [1.0, 1.0, 1.0]
        assert [float(np.asarray(s.network.bandwidth_Bps)) for s in grid[:3]] == [1e5, 1e6, 1e7]


class TestAnalyticVecCoherence:
    # deadline=None: the first example pays the jit compile
    @settings(max_examples=25, deadline=None)
    @given(_point)
    def test_matches_scalar_analytic(self, p):
        lam, s_dev, s_edge, k_edge, mbps, m_dev, m_edge, n_bg = p
        bg = tuple(
            TenantStream(1.0 + i, s_edge * (1 + i), (s_edge / 4) ** 2)
            for i in range(n_bg)
        )
        scn = Scenario(
            workload=Workload(lam, 20_000, 2_000),
            device=Tier("dev", s_dev, service_model=m_dev,
                        service_var=(s_dev / 3) ** 2),
            edges=(EdgeSpec(Tier("edge", s_edge, parallelism_k=k_edge,
                                 service_model=m_edge,
                                 service_var=(s_edge / 3) ** 2),
                            background=bg),),
            network=NetworkPath(mbps * 1e6 / 8),
            allow_unstable=True,
        )
        pred = fleet_analytic(ScenarioBatch.from_scenarios([scn]))
        _assert_matches_scalar(pred, 0, scn)

    def test_100k_batch_single_jitted_call(self):
        # acceptance criterion: >= 100k scenarios in one jitted evaluation,
        # per-scenario results matching the scalar path
        base = _paper_point()
        axes = {
            "network.bandwidth_Bps": np.geomspace(1e5, 1e8, 512),
            "workload.arrival_rate": np.linspace(0.5, 30.0, 256),
        }
        batch = ScenarioBatch.from_sweep(base, axes)
        assert batch.size == 131072 >= 100_000
        pred = fleet_analytic(batch)
        assert pred.t_dev.shape == (131072,)
        assert pred.t_edge.shape == (131072, 1)
        # spot-check random rows against the scalar closed forms
        rng = np.random.default_rng(7)
        bw, lam = axes["network.bandwidth_Bps"], axes["workload.arrival_rate"]
        for idx in rng.integers(0, batch.size, 12):
            i, j = divmod(int(idx), lam.size)
            scn = base.grid({"network.bandwidth_Bps": [bw[i]],
                             "workload.arrival_rate": [lam[j]]})[0]
            _assert_matches_scalar(pred, int(idx), scn)

    def test_return_results_false_drops_return_path(self):
        scn = _paper_point(return_results=False)
        pred = fleet_analytic(ScenarioBatch.from_scenarios([scn]))
        _assert_matches_scalar(pred, 0, scn)

    def test_mmk_erlang_vec_matches_scalar_oracle(self):
        lams = np.array([3.0, 0.5, 10.0, 0.0, 4.9])
        mus = np.array([1.0, 2.0, 1.5, 1.0, 1.0])
        ks = np.array([5.0, 1.0, 8.0, 3.0, 5.0])
        vec = np.asarray(mmk_wait_erlang_vec(lams, mus, ks))
        for i in range(len(lams)):
            ref = mmk_wait_erlang(float(lams[i]), float(mus[i]), int(ks[i]))
            assert vec[i] == pytest.approx(ref, rel=1e-9, abs=1e-12)

    def test_mmk_erlang_vec_refuses_truncated_k(self):
        # regression: k beyond the masked-sum width must fail loudly
        with pytest.raises(ValueError, match="max_k"):
            mmk_wait_erlang_vec(60.0, 1.0, 80.0)
        big = np.asarray(mmk_wait_erlang_vec(60.0, 1.0, 80.0, max_k=128))
        assert float(big) == pytest.approx(mmk_wait_erlang(60.0, 1.0, 80), rel=1e-9)


class TestCrossoverVec:
    def test_bandwidth_crossover_matches_scalar(self):
        scns = [
            _paper_point(allow_unstable=True),
            _paper_point(device=Tier("orin", 0.085), allow_unstable=True),
        ]
        fc = fleet_crossover(ScenarioBatch.from_scenarios(scns), "bandwidth")
        for i, scn in enumerate(scns):
            c = crossovers(scn, "bandwidth")
            assert c.value is not None and fc.found[i]
            assert fc.value[i] == pytest.approx(c.value, rel=1e-6)
            assert bool(fc.offload_wins_above[i]) == c.offload_wins_above

    def test_arrival_rate_crossover_matches_scalar(self):
        scn = Scenario(
            workload=Workload(1.0, 50_000, 2_000),
            device=Tier("dev", 0.010),
            edges=(EdgeSpec(Tier("edge", 0.008, parallelism_k=8.0)),),
            network=NetworkPath(100e6 / 8), allow_unstable=True)
        c = crossovers(scn, "arrival_rate")
        fc = fleet_crossover(ScenarioBatch.from_scenarios([scn]), "arrival_rate")
        assert c.value is not None and fc.found[0]
        assert fc.value[0] == pytest.approx(c.value, rel=1e-6)

    def test_no_crossover_reports_nan(self):
        # offloading wins across the whole default bandwidth range? no — the
        # device here beats the edge everywhere (tiny payload, fast device)
        scn = Scenario(
            workload=Workload(1.0, 1_000, 100),
            device=Tier("fast", 0.001),
            edges=(EdgeSpec(Tier("slow-edge", 0.05)),),
            network=NetworkPath(1e7), allow_unstable=True)
        assert crossovers(scn, "bandwidth").value is None
        fc = fleet_crossover(ScenarioBatch.from_scenarios([scn]), "bandwidth")
        assert not fc.found[0] and np.isnan(fc.value[0])


class TestSimVec:
    def test_lindley_station_exact_vs_station_pass(self):
        rng = np.random.default_rng(3)
        for k in (1, 2, 4):
            arr = np.cumsum(rng.exponential(0.1, size=400))
            svc = rng.exponential(0.05, size=400)
            ref = station_pass(arr, svc, k)
            vec = np.asarray(lindley_station(arr[None, :], svc[None, :], k))[0]
            assert np.max(np.abs(ref - vec)) < 1e-9

    def test_k_max_smaller_than_k_is_refused(self):
        # regression: an undersized server pool must not silently simulate
        # a different station
        arr = np.cumsum(np.full((1, 10), 0.1), axis=1)
        svc = np.full((1, 10), 0.05)
        with pytest.raises(ValueError, match="k_max"):
            lindley_station(arr, svc, 4, k_max=2)

    def test_heterogeneous_k_rows(self):
        rng = np.random.default_rng(4)
        arr = np.cumsum(rng.exponential(0.1, size=(2, 300)), axis=1)
        svc = rng.exponential(0.08, size=(2, 300))
        vec = np.asarray(lindley_station(arr, svc, np.array([1, 3])))
        for i, k in enumerate((1, 3)):
            ref = station_pass(arr[i], svc[i], k)
            assert np.max(np.abs(ref - vec[i])) < 1e-9

    def test_edge_sim_matches_scalar_means(self):
        # shared seeds: deterministic run-to-run, compared within CI bounds
        scn = _paper_point(
            device=Tier("tx2", 0.15, service_model=ServiceModel.EXPONENTIAL),
            edges=(EdgeSpec(Tier("a2", 0.028, parallelism_k=2.0)),),
            workload=Workload(4.0, 30_000, 1_000),
            network=NetworkPath(20e6 / 8))
        batch = ScenarioBatch.from_scenarios([scn] * 3)
        res = simulate_fleet(batch, "edge[0]", n=30_000, seed=5)
        ref = simulate(scn, "edge[0]", n=30_000, seed=5).mean
        pred = float(np.asarray(analytic(scn)["edge[0]"].total))
        assert res.latencies.shape == (3, 30_000)
        for mu in res.mean:
            assert abs(mu - ref) / ref < 0.06
            assert abs(mu - pred) / pred < 0.10

    def test_on_device_sim_matches_scalar_means(self):
        scn = _paper_point()
        batch = ScenarioBatch.from_scenarios([scn] * 2)
        res = simulate_fleet(batch, "on_device", n=30_000, seed=6)
        ref = simulate(scn, "on_device", n=30_000, seed=6).mean
        for mu in res.mean:
            assert abs(mu - ref) / ref < 0.08

    def test_background_edges_are_refused(self):
        scn = _paper_point(edges=(
            EdgeSpec(Tier("a2", 0.028), background=(TenantStream(2.0, 0.028),)),))
        batch = ScenarioBatch.from_scenarios([scn])
        with pytest.raises(ValueError, match="shared-station"):
            simulate_fleet(batch, "edge[0]", n=100)

    def test_fractional_k_is_refused(self):
        scn = _paper_point(edges=(EdgeSpec(Tier("a2", 0.028, parallelism_k=2.5)),))
        batch = ScenarioBatch.from_scenarios([scn])
        with pytest.raises(ValueError, match="fractional"):
            simulate_fleet(batch, "edge[0]", n=100)


class TestTraces:
    def test_step_signal_breakpoints(self):
        t = np.arange(0.0, 10.0, 1.0)
        v = step_signal(t, [(0, 5.0), (4, 1.0), (8, 5.0)])
        assert list(v[:4]) == [5.0] * 4 and list(v[4:8]) == [1.0] * 4
        assert list(v[8:]) == [5.0] * 2

    def test_drift_and_mmpp_are_seeded(self):
        t = np.arange(0.0, 50.0, 1.0)
        a = drift_signal(t, 10.0, 20.0, jitter=0.1, seed=3)
        b = drift_signal(t, 10.0, 20.0, jitter=0.1, seed=3)
        assert np.array_equal(a, b)
        assert np.all(a > 0)
        m1 = mmpp_signal(t, 1.0, 9.0, p_up=0.3, p_down=0.3, seed=1)
        assert np.array_equal(m1, mmpp_signal(t, 1.0, 9.0, p_up=0.3, p_down=0.3, seed=1))
        assert set(np.unique(m1)) <= {1.0, 9.0}
        assert (m1 == 9.0).any()  # bursts actually occur

    def test_trace_validation(self):
        with pytest.raises(ValueError):
            Trace(times=np.array([0.0, 1.0, 3.0]),  # non-uniform
                  bandwidth_Bps=np.ones(3), arrival_rate=np.ones(3),
                  edge_bg_rate=np.zeros((3, 1)))
        with pytest.raises(ValueError):
            make_trace(10.0, 1.0, bandwidth_Bps=0.0, arrival_rate=1.0)

    def test_make_trace_composition(self):
        tr = make_trace(
            60.0, 1.0,
            bandwidth_Bps=lambda t: step_signal(t, [(0, 2.5e6), (30, 2.5e5)]),
            arrival_rate=10.0,
            edge_bg_rate=[lambda t: mmpp_signal(t, 0.0, 30.0, seed=7)],
        )
        assert tr.n_epochs == 60 and tr.n_edges == 1 and tr.epoch_s == 1.0


class TestReplay:
    @staticmethod
    def _trace():
        # bandwidth step (Fig. 6 shape) + tenant churn (Fig. 7 shape)
        return make_trace(
            120.0, 1.0,
            bandwidth_Bps=lambda t: step_signal(
                t, [(0, 20e6 / 8), (40, 0.8e6 / 8), (80, 20e6 / 8)]),
            arrival_rate=2.0,
            edge_bg_rate=[lambda t: step_signal(
                t, [(0, 0.0), (20, 33.0), (35, 0.0)])],
        )

    def test_adaptive_beats_both_statics(self):
        # acceptance criterion: the §5 qualitative result on a bandwidth-step
        # + tenant-churn trace — adaptive mean <= both static policies
        res = replay(_paper_point(network=NetworkPath(20e6 / 8)), self._trace(), seed=1)
        a = res.policies["adaptive"].mean_latency_s
        assert a <= res.policies["on_device"].mean_latency_s
        assert a <= res.policies["edge[0]"].mean_latency_s
        assert res.adaptive_wins
        assert res.policies["adaptive"].switches >= 2  # it actually adapted

    def test_replay_goes_through_estimators_not_raw_values(self):
        res = replay(_paper_point(network=NetworkPath(20e6 / 8)), self._trace(), seed=1)
        step_idx = 40  # bandwidth drops 20 -> 0.8 Mbps here
        true_bw = res.trace.bandwidth_Bps[step_idx]
        # EWMA lag: the manager's view at the step is NOT the raw new value...
        assert res.est_bandwidth_Bps[step_idx] > 2 * true_bw
        # ...but converges within a few epochs
        assert res.est_bandwidth_Bps[step_idx + 8] == pytest.approx(true_bw, rel=0.1)
        # arrival estimates come from the sliding-window estimator (noisy,
        # not the exact trace constant)
        assert not np.allclose(res.est_arrival_rate, res.trace.arrival_rate)

    def test_manager_step_is_the_gateway_decision_path(self):
        # the same metrics through manager.step() and through the gateway
        # must produce the same decision (no duplicated dispatch logic)
        from repro.serving.gateway import OffloadGateway

        scn = _paper_point(network=NetworkPath(20e6 / 8))
        gw = OffloadGateway.from_scenario(scn)
        for dt in np.arange(0.0, 1.0, 0.1):
            gw.observe_arrival(float(dt))
        d_gw = gw.decide(now=1.0)

        mgr = scn.manager()
        d_step = mgr.step(1.0, {
            "workload": scn.workload,
            "lam_dev": gw.arrivals.rate(1.0),
            "bandwidth_Bps": gw.bandwidth.value,
            "edges": [e.state() for e in gw.edges],
        })
        assert d_step.edge_index == d_gw.edge_index
        assert d_step.predicted_latency_s == pytest.approx(d_gw.predicted_latency_s)

    def test_manager_step_missing_metric_raises(self):
        mgr = _paper_point().manager()
        with pytest.raises(KeyError):
            mgr.step(0.0, {"lam_dev": 1.0})

    def test_bg_less_trace_keeps_spec_background(self):
        # regression: a trace without edge columns means "no churn", not
        # "no tenants" — scoring must reflect the spec's declared background
        scn = _paper_point(
            edges=(EdgeSpec(Tier("a2", 0.028),
                            background=(TenantStream(30.0, 0.028),)),),
            network=NetworkPath(20e6 / 8))
        tr = make_trace(20.0, 1.0, bandwidth_Bps=20e6 / 8, arrival_rate=2.0)
        res = replay(scn, tr, seed=0)
        expected = float(np.asarray(analytic(scn)["edge[0]"].total))
        got = res.policies["edge[0]"].mean_latency_s
        assert got == pytest.approx(expected, rel=1e-9)

    def test_trace_edge_count_mismatch_raises(self):
        scn = _paper_point()
        tr = make_trace(20.0, 1.0, bandwidth_Bps=1e6, arrival_rate=2.0,
                        edge_bg_rate=[0.0, 0.0])  # two columns, one edge
        with pytest.raises(ScenarioError):
            replay(scn, tr)


class TestFleetSweepCLI:
    def test_main_writes_report(self, tmp_path, capsys):
        from repro.launch.fleet_sweep import main

        out = tmp_path / "sweep.json"
        rc = main([
            "--axis", "network.bandwidth_Bps=1e5:1e7:8:geom",
            "--axis", "workload.arrival_rate=0.5:6:4",
            "--crossover", "bandwidth",
            "--out", str(out),
        ])
        assert rc == 0
        import json

        report = json.loads(out.read_text())
        assert report["batch_size"] == 32
        assert set(report["strategy_counts"]) <= {"on_device", "edge[0]"}
        assert "crossover" in report
        assert "scenarios/s" in capsys.readouterr().out
