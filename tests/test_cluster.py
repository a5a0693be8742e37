"""Closed-loop cluster tests: spec/trace validation, scalar-manager decision
coherence, the 64-client/4-edge acceptance criteria (equilibrium convergence,
analytic-vs-event-driven MAPE, adaptive <= best static), and the open-loop
bridge (induced scenarios)."""

import numpy as np
import pytest

from repro.core import (
    ClusterSpec,
    EdgeSpec,
    NetworkPath,
    Scenario,
    ScenarioError,
    ServiceModel,
    TenantStream,
    Tier,
    Workload,
    analytic,
)
from repro.core.manager import ON_DEVICE
from repro.core.scenario import implied_service_var
from repro.fleet import (
    Trace,
    TraceBatch,
    cross_check_equilibrium,
    induced_scenario,
    make_trace,
    predict_decisions,
    replay,
    simulate_cluster,
    solve_equilibrium,
    step_signal,
)
from repro.fleet.policy import bg_template
from repro.launch.cluster_sim import default_cluster


def _small_spec(n_clients: int = 5, **base_kw) -> ClusterSpec:
    defaults = dict(
        workload=Workload(2.0, 30_000, 1_000, name="inceptionv4"),
        device=Tier("orin", 0.045),
        edges=(
            EdgeSpec(Tier("a2", 0.028)),
            EdgeSpec(Tier("t4", 0.020, service_model=ServiceModel.EXPONENTIAL)),
        ),
        network=NetworkPath(20e6 / 8),
    )
    defaults.update(base_kw)
    return ClusterSpec(base=Scenario(**defaults), n_clients=n_clients, name="small")


class TestClusterSpec:
    def test_round_trip(self):
        spec = ClusterSpec(base=_small_spec().base, n_clients=3,
                           arrival_scale=(1.0, 0.5, 2.0), name="rt")
        assert ClusterSpec.from_dict(spec.to_dict()) == spec

    def test_validation_named_fields(self):
        base = _small_spec().base
        with pytest.raises(ScenarioError, match="n_clients"):
            ClusterSpec(base=base, n_clients=0)
        with pytest.raises(ScenarioError, match="arrival_scale"):
            ClusterSpec(base=base, n_clients=3, arrival_scale=(1.0, 2.0))
        with pytest.raises(ScenarioError, match=r"arrival_scale\[1\]"):
            ClusterSpec(base=base, n_clients=2, arrival_scale=(1.0, -1.0))
        no_edges = Scenario(workload=base.workload, device=base.device,
                            network=base.network, edges=())
        with pytest.raises(ScenarioError, match="base.edges"):
            ClusterSpec(base=no_edges, n_clients=2)

    def test_from_dict_missing_field_named(self):
        with pytest.raises(ScenarioError, match="n_clients"):
            ClusterSpec.from_dict({"base": _small_spec().base.to_dict()})

    def test_client_views(self):
        spec = ClusterSpec(base=_small_spec().base, n_clients=3,
                           arrival_scale=(1.0, 0.5, 2.0))
        assert np.allclose(spec.arrival_rates(), [2.0, 1.0, 4.0])
        c2 = spec.client(2)
        assert c2.workload.arrival_rate == pytest.approx(4.0)
        assert c2.allow_unstable  # the closed loop may cross saturation
        with pytest.raises(ScenarioError):
            spec.client(3)


class TestTraceBatch:
    def test_from_trace_broadcasts(self):
        tr = make_trace(20.0, 1.0, bandwidth_Bps=1e6, arrival_rate=2.0,
                        edge_bg_rate=[3.0])
        tb = TraceBatch.from_trace(tr, 4)
        assert tb.n_clients == 4 and tb.n_epochs == tr.n_epochs
        assert np.all(tb.bandwidth_Bps == 1e6)
        assert tb.edge_bg_rate.shape == (tr.n_epochs, 1)

    def test_from_traces_stacks_and_validates(self):
        t1 = make_trace(20.0, 1.0, bandwidth_Bps=1e6, arrival_rate=2.0)
        t2 = make_trace(20.0, 1.0, bandwidth_Bps=2e6, arrival_rate=3.0)
        tb = TraceBatch.from_traces([t1, t2])
        assert tb.n_clients == 2
        assert np.all(tb.arrival_rate[:, 1] == 3.0)
        t3 = make_trace(30.0, 1.0, bandwidth_Bps=1e6, arrival_rate=2.0)
        with pytest.raises(ValueError, match="epoch grid"):
            TraceBatch.from_traces([t1, t3])
        t4 = make_trace(20.0, 1.0, bandwidth_Bps=1e6, arrival_rate=2.0,
                        edge_bg_rate=[5.0])
        with pytest.raises(ValueError, match="exogenous"):
            TraceBatch.from_traces([t1, t4])

    def test_domain_validation(self):
        times = np.arange(0.0, 10.0)
        with pytest.raises(ValueError, match="bandwidth"):
            TraceBatch(times=times, bandwidth_Bps=np.zeros((10, 2)),
                       arrival_rate=np.ones((10, 2)), edge_bg_rate=np.zeros((10, 1)))

    def test_client_edge_count_mismatches_raise(self):
        spec = _small_spec(3)
        tr = make_trace(20.0, 1.0, bandwidth_Bps=1e6, arrival_rate=2.0)
        with pytest.raises(ScenarioError, match="traces"):
            simulate_cluster(spec, TraceBatch.from_trace(tr, 2))
        bad_edges = make_trace(20.0, 1.0, bandwidth_Bps=1e6, arrival_rate=2.0,
                               edge_bg_rate=[0.0, 0.0, 0.0])
        with pytest.raises(ScenarioError, match="traces"):
            simulate_cluster(spec, bad_edges)


class TestDecisionCoherence:
    def test_closed_loop_decisions_match_manager_step(self):
        """Every (epoch, client) decision of the vectorized closed loop must
        equal AdaptiveOffloadManager.step() fed the same recorded estimates —
        the one-decision-path guarantee, closed-loop edition."""
        from dataclasses import replace

        spec = _small_spec(4, edges=(
            EdgeSpec(Tier("a2", 0.028)),
            EdgeSpec(Tier("t4", 0.020, service_model=ServiceModel.EXPONENTIAL)),
            EdgeSpec(Tier("mt", 0.015),
                     background=(TenantStream(6.0, 0.015),)),
        ))
        tr = make_trace(
            25.0, 1.0,
            bandwidth_Bps=lambda t: step_signal(t, [(0, 2.5e6), (12, 4e5)]),
            arrival_rate=2.0,
            edge_bg_rate=[0.0, 0.0,
                          lambda t: step_signal(t, [(0, 6.0), (15, 20.0)])],
        )
        res = simulate_cluster(spec, tr, policies=("adaptive",), seed=3)
        base = spec.base
        templates = [bg_template(base, j) for j in range(spec.n_edges)]
        mgr = base.manager()  # hysteresis 0: history cannot change decisions
        choices = res.policies["adaptive"].choices
        checked = 0
        for t in range(tr.n_epochs):
            for i in range(spec.n_clients):
                wl_hat = replace(base.workload,
                                 arrival_rate=float(res.est_arrival_rate[t, i]))
                states = []
                for j, e in enumerate(base.edges):
                    bg = []
                    endo = float(res.est_endo_rate[t, i, j])
                    if endo > 0:
                        bg.append(TenantStream(endo, e.tier.service_time_s,
                                               implied_service_var(e.tier)))
                    exo = float(res.est_exo_rate[t, j])
                    if exo > 0:
                        bg.append(TenantStream(exo, templates[j][1], templates[j][2]))
                    states.append(replace(e, background=tuple(bg)).to_state(wl_hat))
                d = mgr.step(float(t), {
                    "workload": base.workload,
                    "lam_dev": float(res.est_arrival_rate[t, i]),
                    "bandwidth_Bps": float(res.est_bandwidth_Bps[t, i]),
                    "edges": states,
                })
                assert d.edge_index == choices[t, i], (t, i)
                checked += 1
        assert checked == tr.n_epochs * spec.n_clients

    def test_predict_decisions_matches_manager(self):
        """The single-epoch prediction helper agrees with the scalar manager
        on explicit estimates (the gateway coherence building block)."""
        from dataclasses import replace

        spec = _small_spec(1)
        base = spec.base
        for endo in ([0.0, 0.0], [20.0, 0.0], [25.0, 30.0], [60.0, 55.0]):
            choice, t_dev, t_edge = predict_decisions(
                spec, [2.0], [2.5e6], [endo], [0.0, 0.0])
            mgr = base.manager()
            states = []
            for j, e in enumerate(base.edges):
                bg = ((TenantStream(endo[j], e.tier.service_time_s,
                                    implied_service_var(e.tier)),)
                      if endo[j] > 0 else ())
                states.append(replace(e, background=bg).to_state(base.workload))
            d = mgr.step(0.0, {"workload": base.workload, "lam_dev": 2.0,
                               "bandwidth_Bps": 2.5e6, "edges": states})
            assert d.edge_index == choice[0], endo
            assert d.t_dev == pytest.approx(float(t_dev[0]), rel=1e-9)
            for j in range(spec.n_edges):
                assert d.t_edges[j] == pytest.approx(float(t_edge[0, j]), rel=1e-9)


class TestEquilibrium:
    def test_acceptance_64x4_converges_within_budget(self):
        spec = default_cluster(64)
        eq = solve_equilibrium(spec, max_iter=20)
        assert eq.converged
        assert eq.iterations <= 20
        # the fleet actually spreads: more than one target in use
        assert len([c for c in eq.counts().values() if c > 0]) >= 2
        # utilization stays inside the gateable region
        assert np.all(eq.rho_edges <= 0.9)
        assert np.all(np.isfinite(eq.latency_s))

    def test_deterministic(self):
        spec = default_cluster(16)
        a, b = solve_equilibrium(spec), solve_equilibrium(spec)
        assert np.array_equal(a.choices, b.choices)
        assert a.iterations == b.iterations
        assert np.allclose(a.latency_s, b.latency_s)

    def test_no_oscillation_on_uncontended_cluster(self):
        # plenty of capacity for 4 clients: plain best response suffices
        eq = solve_equilibrium(_small_spec(4))
        assert eq.converged and not eq.oscillation

    def test_max_iter_respected(self):
        eq = solve_equilibrium(default_cluster(64), max_iter=1)
        assert eq.iterations == 1
        assert not eq.converged

    def test_fixed_point_is_self_consistent(self):
        """At the fixed point, no client can improve by deviating — checked
        against the full response table."""
        spec = default_cluster(32)
        eq = solve_equilibrium(spec)
        assert eq.converged
        lam = spec.arrival_rates()
        for i in range(spec.n_clients):
            chosen = eq.latency_s[i]
            scn = induced_scenario(spec, eq.choices, i, allow_unstable=True)
            totals = analytic(scn).totals()
            best = min(totals.values())
            assert chosen <= best * (1 + 1e-9), (i, chosen, totals)
        assert np.allclose(eq.edge_loads.sum(), lam[eq.choices >= 0].sum())


class TestInducedScenario:
    def test_per_client_background_streams(self):
        spec = default_cluster(16)
        eq = solve_equilibrium(spec)
        offloaders = np.nonzero(eq.choices >= 0)[0]
        rep = int(offloaders[0])
        j = int(eq.choices[rep])
        scn = induced_scenario(spec, eq.choices, rep)
        same_edge = [c for c in offloaders if int(eq.choices[c]) == j and c != rep]
        assert len(scn.edges[j].background) == len(same_edge)
        # own stream excluded, everyone else's present once
        names = {t.name for t in scn.edges[j].background}
        assert f"cluster-client[{rep}]" not in names

    def test_open_loop_bridge_matches_equilibrium_latency(self):
        """analytic() on the induced scenario reproduces the closed-loop
        latency at the fixed point — the scalar and vectorized closed forms
        meet across the loop boundary."""
        spec = default_cluster(24)
        eq = solve_equilibrium(spec)
        for i in (0, spec.n_clients // 2, spec.n_clients - 1):
            scn = induced_scenario(spec, eq.choices, i, allow_unstable=True)
            tgt = int(eq.choices[i])
            key = "on_device" if tgt == ON_DEVICE else f"edge[{tgt}]"
            total = float(np.asarray(analytic(scn).totals()[key]))
            assert total == pytest.approx(float(eq.latency_s[i]), rel=1e-9)


class TestCrossCheck:
    def test_solver_overrides_flow_into_the_cross_check(self):
        """cross_check must evaluate the system the fixed point was solved
        for: rate/bandwidth overrides ride on the Equilibrium itself."""
        spec = _small_spec(4)
        lam = 1.5 * spec.arrival_rates()
        eq = solve_equilibrium(spec, arrival_rates=lam, bandwidth_Bps=1.5e6)
        assert np.allclose(eq.arrival_rates, lam)
        assert np.allclose(eq.bandwidth_Bps, 1.5e6)
        cc = cross_check_equilibrium(spec, eq, n=8_000, seed=0)
        for g in cc["groups"]:
            assert g["arrival_rate"] == pytest.approx(3.0)

    def test_predict_decisions_idle_estimate_falls_back_to_spec_rate(self):
        spec = _small_spec(2)
        choice, t_dev, t_edge = predict_decisions(
            spec, [0.0, 2.0], [2.5e6, 2.5e6],
            np.zeros((2, 2)), [0.0, 0.0])
        assert np.all(np.isfinite(t_dev))
        assert np.all(np.isfinite(t_edge))
        assert choice[0] == choice[1]  # idle client priced at the spec rate
        with pytest.raises(ScenarioError, match="n_clients"):
            predict_decisions(spec, [2.0], [2.5e6], [[0.0, 0.0]], [0.0, 0.0])

    def test_acceptance_analytic_vs_event_driven(self):
        """Acceptance criterion: closed-loop analytic means within 5% MAPE of
        the event-driven simulators at rho <= 0.9, on the seeded 64x4 spec."""
        spec = default_cluster(64)
        eq = solve_equilibrium(spec)
        assert eq.converged
        cc = cross_check_equilibrium(spec, eq, n=60_000, seed=0)
        assert cc["n_groups"] >= 2
        gated = [g for g in cc["groups"] if g["gated"]]
        assert gated, "the 64x4 spec must produce gated (rho<=0.9) groups"
        assert cc["gated_max_mape_pct"] <= 5.0, cc["groups"]


class TestClosedLoop:
    @staticmethod
    def _step_trace(duration=120.0, bw0=20e6 / 8, drop=0.15):
        third = duration / 3
        return make_trace(
            duration, 1.0,
            bandwidth_Bps=lambda t: step_signal(
                t, [(0, bw0), (third, bw0 * drop), (2 * third, bw0)]),
            arrival_rate=2.0,
        )

    def test_acceptance_adaptive_beats_every_static(self):
        spec = default_cluster(64)
        policies = ("adaptive", "on_device") + tuple(
            f"edge[{j}]" for j in range(spec.n_edges))
        res = simulate_cluster(spec, self._step_trace(), policies=policies,
                               stagger=8, seed=1)
        a = res.policies["adaptive"].mean_latency_s
        for name, p in res.policies.items():
            if name != "adaptive":
                assert a <= p.mean_latency_s, (name, a, p.mean_latency_s)
        assert res.adaptive_wins
        assert res.policies["adaptive"].saturated_epochs == 0

    def test_adapts_to_bandwidth_dip(self):
        """During the dip offloading is not worth 0.08 s of transfer: the
        whole fleet should be back on-device mid-trace, and offloading again
        at the end."""
        spec = default_cluster(64)
        res = simulate_cluster(spec, self._step_trace(), policies=("adaptive",),
                               stagger=8, seed=1)
        choices = res.policies["adaptive"].choices
        assert np.all(choices[60] == ON_DEVICE)  # mid-dip
        assert np.mean(choices[-1] >= 0) > 0.5  # recovered

    def test_statics_saturate_shared_edges(self):
        # 128 rps on any single edge exceeds every edge's capacity: the
        # all-on-one-edge statics saturate every client-epoch
        spec = default_cluster(64)
        tr = make_trace(30.0, 1.0, bandwidth_Bps=20e6 / 8, arrival_rate=2.0)
        res = simulate_cluster(spec, tr, policies=("edge[1]",))
        p = res.policies["edge[1]"]
        assert p.saturated_epochs == p.latencies_s.size

    def test_endogenous_loads_account_for_every_offloader(self):
        spec = default_cluster(32)
        res = simulate_cluster(spec, self._step_trace(60.0), policies=("adaptive",),
                               stagger=4, seed=2)
        p = res.policies["adaptive"]
        lam = res.traces.arrival_rate
        for t in (0, 20, 40, 59):
            offloaded = lam[t][p.choices[t] >= 0].sum()
            assert p.edge_loads[t].sum() == pytest.approx(offloaded)

    def test_single_client_cluster_matches_scalar_replay_statics(self):
        """With N=1 and no endogenous contention, the cluster scorer must
        reproduce the scalar replay's closed-form policy scores exactly."""
        spec = _small_spec(1)
        tr = self._step_trace(60.0)
        res = simulate_cluster(spec, tr, policies=("on_device", "edge[0]", "edge[1]"))
        rep = replay(spec.client(0), tr,
                     policies=("on_device", "edge[0]", "edge[1]"), seed=0)
        for name in ("on_device", "edge[0]", "edge[1]"):
            a = res.policies[name].latencies_s[:, 0]
            b = rep.policies[name].latencies_s
            np.testing.assert_allclose(a, b, rtol=1e-9)

    def test_same_seed_same_run(self):
        spec = _small_spec(6)
        tr = self._step_trace(40.0)
        r1 = simulate_cluster(spec, tr, seed=7, stagger=3)
        r2 = simulate_cluster(spec, tr, seed=7, stagger=3)
        assert np.array_equal(r1.policies["adaptive"].choices,
                              r2.policies["adaptive"].choices)
        np.testing.assert_array_equal(r1.est_arrival_rate, r2.est_arrival_rate)

    def test_stagger_bounds_validated(self):
        spec = _small_spec(4)
        tr = self._step_trace(30.0)
        with pytest.raises(ValueError, match="stagger"):
            simulate_cluster(spec, tr, stagger=0)
        with pytest.raises(ValueError, match="stagger"):
            simulate_cluster(spec, tr, stagger=5)

    def test_throughput_sanity(self):
        """The jitted loop must stay in vectorized territory (the bench
        asserts the real >=100k/s headline; this is a generous CI floor)."""
        import time

        spec = default_cluster(64)
        tr = make_trace(500.0, 1.0, bandwidth_Bps=20e6 / 8, arrival_rate=2.0)
        simulate_cluster(spec, tr, policies=("adaptive",), stagger=8)  # compile
        t0 = time.perf_counter()
        res = simulate_cluster(spec, tr, policies=("adaptive",), stagger=8, seed=1)
        rate = res.client_epochs / (time.perf_counter() - t0)
        assert rate >= 30_000, f"{rate:.0f} client-epochs/s"


class TestShardedScan:
    """``shards=k`` must reproduce ``shards=1`` exactly: decisions within an
    epoch depend only on lagged load reports, the Poisson chain is drawn once
    before blocking, and the endogenous total is restored by a psum — so
    blocking re-associates one float sum and changes nothing else."""

    @staticmethod
    def _run(shards, n=12):
        spec = default_cluster(n)
        tr = make_trace(
            60.0, 1.0,
            bandwidth_Bps=lambda t: step_signal(t, [(0, 2.5e6), (30, 6e5)]),
            arrival_rate=2.0,
        )
        return simulate_cluster(spec, tr, policies=("adaptive",), stagger=3,
                                hysteresis=0.05, seed=7, shards=shards)

    def _assert_exact(self, ref, res):
        a, b = ref.policies["adaptive"], res.policies["adaptive"]
        assert np.array_equal(a.choices, b.choices)
        assert np.allclose(a.latencies_s, b.latencies_s, rtol=1e-12, atol=0)
        assert np.allclose(a.edge_loads, b.edge_loads, rtol=1e-12, atol=1e-12)
        assert np.allclose(ref.est_endo_rate, res.est_endo_rate,
                           rtol=1e-12, atol=1e-15)
        assert np.allclose(ref.est_arrival_rate, res.est_arrival_rate,
                           rtol=1e-12, atol=0)

    def test_blocked_matches_flat(self):
        ref = self._run(1)
        # a meaningless comparison unless the loop actually couples clients
        assert ref.policies["adaptive"].offload_frac > 0
        self._assert_exact(ref, self._run(4))

    def test_padding_is_exact(self):
        # 5 does not divide 12: two blocks carry inert zero-rate dummies
        self._assert_exact(self._run(1), self._run(5))

    def test_shards_validated(self):
        spec = default_cluster(4)
        tr = make_trace(10.0, 1.0, bandwidth_Bps=1e6, arrival_rate=2.0)
        with pytest.raises(ValueError, match="shards"):
            simulate_cluster(spec, tr, shards=0)
        with pytest.raises(ValueError, match="shards"):
            simulate_cluster(spec, tr, shards=5)

    def test_shard_map_on_forced_multidevice(self):
        """The true multi-device path (shard_map over a 4-CPU mesh) agrees
        with the flat scan — run in a subprocess because device count is
        fixed at jax import."""
        import os
        import subprocess
        import sys

        import repro

        # repro is a namespace package (no __init__.py): locate via __path__
        src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
        script = (
            "import jax, numpy as np\n"
            "assert len(jax.devices()) == 4, jax.devices()\n"
            "from repro.fleet import make_trace, simulate_cluster, step_signal\n"
            "from repro.launch.cluster_sim import default_cluster\n"
            "spec = default_cluster(8)\n"
            "tr = make_trace(30.0, 1.0,\n"
            "    bandwidth_Bps=lambda t: step_signal(t, [(0, 2.5e6), (15, 6e5)]),\n"
            "    arrival_rate=2.0)\n"
            "kw = dict(policies=('adaptive',), stagger=2, seed=7)\n"
            "a = simulate_cluster(spec, tr, **kw).policies['adaptive']\n"
            "b = simulate_cluster(spec, tr, shards=4, **kw).policies['adaptive']\n"
            "assert np.array_equal(a.choices, b.choices)\n"
            "assert np.allclose(a.latencies_s, b.latencies_s, rtol=1e-12)\n"
            "print('SHARDMAP_OK')\n"
        )
        env = dict(os.environ,
                   JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   PYTHONPATH=src)
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr
        assert "SHARDMAP_OK" in proc.stdout


class TestClusterCLI:
    def test_main_writes_report(self, tmp_path, capsys):
        from repro.launch.cluster_sim import main

        out = tmp_path / "cluster.json"
        rc = main(["--clients", "16", "--duration", "45", "--out", str(out)])
        assert rc == 0
        import json

        report = json.loads(out.read_text())
        assert report["equilibrium"]["converged"]
        assert report["replay"]["adaptive_wins"]
        assert report["replay"]["client_epochs"] == 16 * 45
        assert "client-epochs/s" in capsys.readouterr().out
