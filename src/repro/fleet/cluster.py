"""Closed-loop multi-client edge-cluster simulation (the paper's §6 setting
at fleet scale).

``repro.fleet.replay`` scores ONE client against exogenous traces — nothing
that client does changes the load anyone else observes. A real multi-tenant
edge deployment is coupled: when a client offloads, its stream joins the
chosen edge's aggregate, every other client's model of that edge worsens,
and their next decisions shift load elsewhere. This module closes that loop
for N clients sharing E edge servers over T epochs:

  * every epoch, every client decides on-device vs offload(e) with exactly
    the §4.2 estimator path the scalar :class:`AdaptiveOffloadManager.step`
    runs — EWMA bandwidth and edge-load reports, a sliding-window arrival
    estimate over seeded Poisson counts — transcribed to (N,)/(N, E) arrays
    (a coherence test pins the two paths decision-for-decision);
  * the per-edge background load is *endogenous*: the offloaders' arrival
    rates superpose (``multitenant.mixture_moments``, §3.4) on top of any
    exogenous background from the trace, and the resulting loads are what
    next epoch's estimators observe;
  * per-client expected latency under the TRUE conditions is evaluated with
    the jitted ``analytic_vec`` closed forms over (N, E) arrays — the
    decision loop is a single ``lax.scan`` over epochs and the scoring a
    single jitted call over all T*N client-epochs, which is what makes
    >=100k client-epochs/s on CPU routine;
  * :func:`solve_equilibrium` finds the fixed point of the decision->load
    map under constant conditions (synchronous best response, falling back
    to damped one-client-at-a-time switching when an oscillation is
    detected), and :func:`cross_check_equilibrium` validates the closed-loop
    analytic means against the event-driven simulators exactly the way the
    PR 3 differential harness validated the open-loop ones.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.latency import NetworkPath
from repro.core.manager import ON_DEVICE
from repro.core.multitenant import TenantStream, mixture_moments
from repro.core.scenario import (
    ClusterSpec,
    Scenario,
    ScenarioError,
    analytic as scalar_analytic,
    implied_service_var,
)
from repro.core.simulation import steady_slice
from repro.core.tail import euler_grow_iters, resolve_tail_method
from repro.jaxenv import x64

from .analytic_vec import (
    _device_latency_vec,
    _edge_latency_vec,
    _implied_var_vec,
    _proc_wait_vec,
    mg1_wait_vec,
    mm1_wait_vec,
)
from .batch import MODEL_CODES, ScenarioBatch
from .policy import bg_template, clamp_saturation, parse_policy
from .sim_vec import simulate_fleet
from .tail_vec import (
    KIND_EXP,
    KIND_GAMMA,
    _device_tail_vec,
    _edge_tail_vec,
    _stack_stations,
    sojourn_quantile_vec,
)
from .traces import Trace, TraceBatch

__all__ = [
    "ClusterPolicyResult",
    "ClusterResult",
    "Equilibrium",
    "simulate_cluster",
    "solve_equilibrium",
    "induced_scenario",
    "cross_check_equilibrium",
    "predict_decisions",
    "predict_terms",
]


# ---------------------------------------------------------------------------
# static spec arrays
# ---------------------------------------------------------------------------


def _spec_arrays(spec: ClusterSpec) -> dict[str, np.ndarray]:
    """The client-independent columns every cluster evaluation consumes."""
    base = spec.base
    e_n = spec.n_edges
    edge_s = np.array([e.tier.service_time_s for e in base.edges])
    templates = [bg_template(base, j) for j in range(e_n)]
    return {
        "lam_spec": spec.arrival_rates(),  # (N,)
        "req_bytes": np.float64(base.workload.req_bytes),
        "res_bytes": np.float64(base.workload.res_bytes),
        "return_results": np.bool_(base.return_results),
        "dev_s": np.float64(base.device.service_time_s),
        "dev_k": np.float64(base.device.parallelism_k),
        "dev_var": np.float64(base.device.service_var),
        "dev_model": np.int8(MODEL_CODES[base.device.service_model]),
        "edge_s": edge_s,
        "edge_k": np.array([e.tier.parallelism_k for e in base.edges]),
        "edge_var": np.array([e.tier.service_var for e in base.edges]),
        "edge_model": np.array(
            [MODEL_CODES[e.tier.service_model] for e in base.edges], dtype=np.int8),
        "edge_bw": np.array(
            [np.nan if e.bandwidth_Bps is None else e.bandwidth_Bps
             for e in base.edges]),
        # endogenous template: what one unit of *cluster* load looks like on
        # edge j — the shared workload's own service moments there
        "endo_mean": edge_s,
        "endo_var": np.array([implied_service_var(e.tier) for e in base.edges]),
        # exogenous template: the spec's declared background mixture, whose
        # rate the trace churns while the service moments hold (cf. replay)
        "exo_rate": np.array([t[0] for t in templates]),
        "exo_mean": np.array([t[1] for t in templates]),
        "exo_var": np.array([t[2] for t in templates]),
    }


def _as_jnp(cst: Mapping[str, np.ndarray]) -> dict[str, jnp.ndarray]:
    return {k: jnp.asarray(v) for k, v in cst.items()}


# ---------------------------------------------------------------------------
# Algorithm 1 over (N, E) arrays — the manager's prediction path, transcribed
# ---------------------------------------------------------------------------


def _bg_moments(cst, endo, exo):
    """The (bg_lam, bg_wsum, bg_ssum) background columns from endogenous and
    exogenous per-edge rates, each expanded with its own service template —
    THE mixture-moment expansion, shared by the prediction path, the decision
    scan, and the truth-scoring tables so the three can never drift apart.
    ``endo``/``exo`` broadcast against the (E,) templates ((N, E), (T, N, E),
    (1, E), ... all work)."""
    bg_lam = endo + exo
    bg_wsum = endo * cst["endo_mean"] + exo * cst["exo_mean"]
    bg_ssum = endo * (cst["endo_var"] + cst["endo_mean"] ** 2) + exo * (
        cst["exo_var"] + cst["exo_mean"] ** 2)
    return bg_lam, bg_wsum, bg_ssum


def _predict_terms_vec(cst, lam_hat, bw_hat, bg_lam, bg_wsum, bg_ssum):
    """The per-term decomposition behind :func:`_predict_vec`, keyed exactly
    like ``LatencyBreakdown`` (w_proc_dev/s_dev; w_net_dev/n_req/w_proc_edge/
    s_edge/w_net_edge/n_res) — device terms (N,), edge terms (N, E). The
    totals are DERIVED from these by ordered summation, so the cluster's
    decision audits re-sum bit-exactly by construction."""
    shape = jnp.broadcast_shapes(lam_hat.shape + (1,), bg_lam.shape)
    w_proc_dev = _proc_wait_vec(
        cst["dev_model"], lam_hat, cst["dev_s"], cst["dev_var"], cst["dev_k"])
    s_dev = jnp.broadcast_to(cst["dev_s"], lam_hat.shape)

    own_var = _implied_var_vec(cst["edge_model"], cst["edge_s"], cst["edge_var"])
    lam = lam_hat[:, None]
    lam_tot = lam + bg_lam
    mean_mix = (lam * cst["edge_s"] + bg_wsum) / lam_tot
    second = (lam * (own_var + cst["edge_s"] ** 2) + bg_ssum) / lam_tot
    var_mix = jnp.maximum(0.0, second - mean_mix**2)
    w_proc_edge = jnp.broadcast_to(
        mg1_wait_vec(lam_tot, 1.0 / mean_mix, var_mix, cst["edge_k"]), shape)

    b = jnp.where(jnp.isnan(cst["edge_bw"]), bw_hat[:, None], cst["edge_bw"])
    w_net_dev = jnp.broadcast_to(
        mm1_wait_vec(lam, b / cst["req_bytes"]), shape)
    n_req = jnp.broadcast_to(cst["req_bytes"] / b, shape)
    use_res = cst["return_results"] & (cst["res_bytes"] > 0)
    w_net_edge = jnp.where(
        use_res, mm1_wait_vec(lam_tot, b / cst["res_bytes"]), 0.0)
    n_res = jnp.where(use_res, jnp.broadcast_to(cst["res_bytes"] / b, shape), 0.0)
    return {
        "w_proc_dev": w_proc_dev,
        "s_dev": s_dev,
        "w_net_dev": w_net_dev,
        "n_req": n_req,
        "w_proc_edge": w_proc_edge,
        "s_edge": jnp.broadcast_to(cst["edge_s"], shape),
        "w_net_edge": w_net_edge,
        "n_res": n_res,
    }


def _sum_terms(terms):
    """(t_dev, t_edge) from the term dict — LatencyBreakdown's exact
    summation order (matches the scalar manager's ordered sum)."""
    t_dev = terms["w_proc_dev"] + terms["s_dev"]
    t_edge = (terms["w_net_dev"] + terms["n_req"] + terms["w_proc_edge"]
              + terms["s_edge"] + terms["w_net_edge"] + terms["n_res"])
    return t_dev, t_edge


def _predict_vec(cst, lam_hat, bw_hat, bg_lam, bg_wsum, bg_ssum):
    """(N,) t_dev and (N, E) t_edge exactly as ``AdaptiveOffloadManager.step``
    computes them from the same estimates (Alg. 1 lines 1-6): the device via
    its service-model dispatch, each edge as M/G/1 on the aggregate mixture
    (own stream folded in) with the OWN service time on line 6."""
    return _sum_terms(
        _predict_terms_vec(cst, lam_hat, bw_hat, bg_lam, bg_wsum, bg_ssum))


def _predict_tail_vec(cst, lam_hat, bw_hat, bg_lam, bg_wsum, bg_ssum, q,
                      method: str, grow_iters: int | None = None):
    """The q-quantile twin of :func:`_predict_vec`: the same station
    composition an SLO-mode ``AdaptiveOffloadManager`` prices scalar-side
    (device NIC -> aggregate-mixture M/G/1 wait + OWN service -> return NIC),
    vectorized over (N, E). Coherence with ``manager.decide`` under
    ``slo_quantile`` is pinned by tests exactly like the mean path."""
    n = lam_hat.shape[0]
    e_n = cst["edge_s"].shape[0]
    dev_kind = jnp.broadcast_to(cst["dev_model"], (n,)).astype(jnp.int8)
    t_dev = sojourn_quantile_vec(_stack_stations({
        "lam": lam_hat,
        "wkind": dev_kind,
        "wmean": jnp.broadcast_to(cst["dev_s"] / cst["dev_k"], (n,)),
        "wvar": jnp.broadcast_to(cst["dev_var"], (n,)),
        "fkind": dev_kind,
        "fmean": jnp.broadcast_to(cst["dev_s"], (n,)),
        "fvar": jnp.broadcast_to(cst["dev_var"], (n,)),
    }), q, method=method, slot_kinds=(None,), grow_iters=grow_iters)

    own_var = _implied_var_vec(cst["edge_model"], cst["edge_s"], cst["edge_var"])
    lam = lam_hat[:, None]
    lam_tot = lam + bg_lam
    mean_mix = (lam * cst["edge_s"] + bg_wsum) / lam_tot
    second = (lam * (own_var + cst["edge_s"] ** 2) + bg_ssum) / lam_tot
    var_mix = jnp.maximum(0.0, second - mean_mix**2)

    b = jnp.where(jnp.isnan(cst["edge_bw"]), bw_hat[:, None], cst["edge_bw"])
    req_mean = cst["req_bytes"] / b
    use_res = cst["return_results"] & (cst["res_bytes"] > 0)
    res_mean = jnp.where(use_res, cst["res_bytes"] / b, 0.0)
    shape = (n, e_n)
    kexp = jnp.full(shape, KIND_EXP, dtype=jnp.int8)
    kgam = jnp.full(shape, KIND_GAMMA, dtype=jnp.int8)
    zero = jnp.zeros(shape)
    lam_e = jnp.broadcast_to(lam, shape)
    stations = _stack_stations(
        {"lam": lam_e, "wkind": kexp, "wmean": req_mean, "wvar": zero,
         "fkind": kexp, "fmean": req_mean, "fvar": zero},
        {"lam": lam_tot, "wkind": kgam, "wmean": mean_mix / cst["edge_k"],
         "wvar": var_mix, "fkind": kgam,
         "fmean": jnp.broadcast_to(cst["edge_s"], shape), "fvar": var_mix},
        {"lam": lam_tot, "wkind": kexp, "wmean": res_mean, "wvar": zero,
         "fkind": kexp, "fmean": res_mean, "fvar": zero},
    )
    t_edge = sojourn_quantile_vec(stations, q, method=method,
                                  slot_kinds=("nic", None, "nic"),
                                  grow_iters=grow_iters)
    return t_dev, t_edge


def _tail_grow_iters(slo_quantile: float, tail_method: str) -> int | None:
    """Static bracket-doubling count for the euler tail path (None for the
    asymptote) — computed where ``slo_quantile`` is still a Python float so
    the jitted paths can pass it through as a static argument."""
    return euler_grow_iters(slo_quantile) if tail_method == "euler" else None


def _decide_vec(t_dev, t_edge, prev_choice, hysteresis, use_hysteresis):
    """Vectorized ``manager.apply_decision_rule``: first-argmin with
    on-device winning ties, plus the relative-improvement hysteresis."""
    stacked = jnp.concatenate([t_dev[:, None], t_edge], axis=1)
    choice = jnp.argmin(stacked, axis=1) - 1
    predicted = jnp.min(stacked, axis=1)
    prev_t = jnp.take_along_axis(stacked, (prev_choice + 1)[:, None], axis=1)[:, 0]
    keep = (
        use_hysteresis
        & (hysteresis > 0.0)
        & (choice != prev_choice)
        & jnp.isfinite(prev_t)
        & (predicted > (1.0 - hysteresis) * prev_t)
    )
    return jnp.where(keep, prev_choice, choice).astype(jnp.int32)


def predict_decisions(
    spec: ClusterSpec,
    lam_hat,
    bandwidth_hat,
    endo_hat,
    exo_hat,
    *,
    prev_choice=None,
    hysteresis: float = 0.0,
    slo_quantile: float | None = None,
    tail_method: str = "asymptote",
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One epoch of cluster decisions from explicit estimates.

    ``lam_hat``/``bandwidth_hat`` are (N,) per-client estimates, ``endo_hat``
    the (N, E) estimated *other-client* load per edge, ``exo_hat`` the (E,)
    estimated exogenous background. Returns ``(choices, t_dev, t_edge)`` —
    the same numbers ``AdaptiveOffloadManager.step`` produces client by
    client from identical inputs, which is exactly what the gateway
    multi-edge coherence tests assert. Non-positive arrival estimates fall
    back to the client's spec rate, exactly like the closed-loop scan (an
    idle estimator must not poison the mixture mean with 0/0)."""
    if slo_quantile is not None:
        if not 0.0 < slo_quantile < 1.0:
            raise ValueError(f"slo_quantile must be in (0, 1), got {slo_quantile}")
        tail_method = resolve_tail_method(slo_quantile, tail_method)
    cst = _spec_arrays(spec)
    with x64():
        c = _as_jnp(cst)
        lam_hat = jnp.atleast_1d(jnp.asarray(lam_hat, dtype=jnp.float64))
        if lam_hat.shape[0] != spec.n_clients:
            raise ScenarioError(
                "n_clients", f"expected {spec.n_clients} per-client estimates, "
                f"got {lam_hat.shape[0]}")
        lam_hat = jnp.where(lam_hat > 0, lam_hat, c["lam_spec"])
        bw_hat = jnp.broadcast_to(
            jnp.asarray(bandwidth_hat, dtype=jnp.float64), lam_hat.shape)
        endo = jnp.asarray(endo_hat, dtype=jnp.float64).reshape(
            lam_hat.shape[0], spec.n_edges)
        exo = jnp.asarray(exo_hat, dtype=jnp.float64).reshape(spec.n_edges)
        bg_lam, bg_wsum, bg_ssum = _bg_moments(c, endo, exo[None, :])
        if slo_quantile is None:
            t_dev, t_edge = _predict_vec(c, lam_hat, bw_hat, bg_lam, bg_wsum, bg_ssum)
        else:
            t_dev, t_edge = _predict_tail_vec(
                c, lam_hat, bw_hat, bg_lam, bg_wsum, bg_ssum,
                jnp.float64(slo_quantile), tail_method,
                _tail_grow_iters(slo_quantile, tail_method))
        if prev_choice is None:
            prev = jnp.full(lam_hat.shape, ON_DEVICE, dtype=jnp.int32)
            use_h = jnp.bool_(False)
        else:
            prev = jnp.asarray(prev_choice, dtype=jnp.int32)
            use_h = jnp.bool_(True)
        choice = _decide_vec(t_dev, t_edge, prev, jnp.float64(hysteresis), use_h)
        return np.asarray(choice), np.asarray(t_dev), np.asarray(t_edge)


def predict_terms(
    spec: ClusterSpec,
    lam_hat,
    bandwidth_hat,
    endo_hat,
    exo_hat,
) -> dict[str, np.ndarray]:
    """The per-term decomposition behind one epoch of (mean-mode) cluster
    decisions — ``predict_decisions``' totals, shown working.

    Same estimate inputs and fallback semantics as :func:`predict_decisions`.
    Returns LatencyBreakdown-keyed arrays — device terms ``w_proc_dev``/
    ``s_dev`` (N,), edge terms ``w_net_dev``/``n_req``/``w_proc_edge``/
    ``s_edge``/``w_net_edge``/``n_res`` (N, E) — plus their ordered sums
    ``t_dev`` (N,) and ``t_edge`` (N, E), which match ``predict_decisions``
    bit-for-bit on identical inputs (both are ``_sum_terms`` over
    ``_predict_terms_vec``). This is what ``repro.obs.audit.audit_cluster``
    reconstructs closed-loop decision audits from.
    """
    cst = _spec_arrays(spec)
    with x64():
        c = _as_jnp(cst)
        lam_hat = jnp.atleast_1d(jnp.asarray(lam_hat, dtype=jnp.float64))
        if lam_hat.shape[0] != spec.n_clients:
            raise ScenarioError(
                "n_clients", f"expected {spec.n_clients} per-client estimates, "
                f"got {lam_hat.shape[0]}")
        lam_hat = jnp.where(lam_hat > 0, lam_hat, c["lam_spec"])
        bw_hat = jnp.broadcast_to(
            jnp.asarray(bandwidth_hat, dtype=jnp.float64), lam_hat.shape)
        endo = jnp.asarray(endo_hat, dtype=jnp.float64).reshape(
            lam_hat.shape[0], spec.n_edges)
        exo = jnp.asarray(exo_hat, dtype=jnp.float64).reshape(spec.n_edges)
        bg_lam, bg_wsum, bg_ssum = _bg_moments(c, endo, exo[None, :])
        terms = _predict_terms_vec(c, lam_hat, bw_hat, bg_lam, bg_wsum, bg_ssum)
        t_dev, t_edge = _sum_terms(terms)
        out = {k: np.asarray(v) for k, v in terms.items()}
        out["t_dev"] = np.asarray(t_dev)
        out["t_edge"] = np.asarray(t_edge)
        return out


# ---------------------------------------------------------------------------
# the closed decision loop: one lax.scan over epochs
# ---------------------------------------------------------------------------


@jax.jit
def _poisson_counts(seed, lam_true, dt):
    """Per-epoch Poisson arrival counts (T, N), hoisted out of the decision
    scan. Replicates the scan's original in-carry key chain step for step
    (``key, kp = split(key); poisson(kp, lam_t * dt)``) so the draws are
    bitwise identical to what the pre-hoist closed loop sampled — which is
    what lets the sharded scans consume the SAME counts as the flat one and
    stay exact, and lets padding happen after sampling without perturbing the
    real clients' draws."""

    def chain(key, lam_t):
        key, kp = jax.random.split(key)
        return key, jax.random.poisson(kp, lam_t * dt).astype(jnp.float64)

    _, n_req = jax.lax.scan(chain, jax.random.PRNGKey(seed), lam_true)
    return n_req


def _scan_epochs(cst, lam_spec, cohort, bw_true, lam_true, exo_true, n_req_all,
                 *, window: int, stagger: int, dt, bw_alpha, bg_alpha,
                 hysteresis, slo_q: float | None = None,
                 tail_method: str = "asymptote", axis_name: str | None = None):
    """The closed decision loop over THIS shard's clients: one ``lax.scan``
    over epochs.

    Carry: per-client EWMA bandwidth, the sliding-window ring of per-epoch
    Poisson arrival counts (pre-drawn by :func:`_poisson_counts` and fed in
    as scan inputs), per-client EWMA estimates of the *other* clients'
    per-edge load (fed by last epoch's reports — the closed loop's one-epoch
    information lag), the shared EWMA exogenous-load estimate, and the
    previous decision (hysteresis).

    Within an epoch every per-client quantity is elementwise in the client
    axis; the ONLY cross-client coupling is the endogenous-load total, so
    with ``axis_name`` set the same body runs on a block of clients under
    ``shard_map`` (or ``vmap`` on one device) and a single ``lax.psum``
    restores the fleet-wide sum — blocking is exact, not approximate.

    ``stagger`` desynchronizes the control epochs: client i re-decides only
    on epochs where ``t % stagger == i % stagger`` and holds its previous
    target in between. Synchronized fleets sharing identical estimates herd
    — every client stampedes onto the same momentarily-cheapest edge,
    saturates it, and stampedes off again, paying the saturation penalty in
    lockstep. Real per-device managers are not phase-locked; ``stagger=k``
    models k staggered cohorts (1 = fully synchronous, the single-client
    replay semantics).
    """
    t_n, n = lam_true.shape
    e_n = exo_true.shape[1]

    def step(carry, inputs):
        est_bw, counts, est_endo, est_exo, prev_choice = carry
        bw_t, lam_t, exo_t, n_req, idx = inputs
        first = idx == 0

        # -- telemetry (§4.2): estimators, never raw instantaneous values --
        est_bw = jnp.where(first, bw_t, bw_alpha * bw_t + (1 - bw_alpha) * est_bw)
        est_exo = jnp.where(first, exo_t, bg_alpha * exo_t + (1 - bg_alpha) * est_exo)
        counts = jax.lax.dynamic_update_slice(
            counts, n_req[:, None], (0, jnp.mod(idx, window)))
        rate = counts.sum(axis=1) / (window * dt)
        lam_hat = jnp.where(rate > 0, rate, lam_spec)

        # -- Algorithm 1 on the estimated state (mean or SLO-quantile) -----
        bg_lam, bg_wsum, bg_ssum = _bg_moments(cst, est_endo, est_exo[None, :])
        if slo_q is None:
            t_dev, t_edge = _predict_vec(cst, lam_hat, est_bw,
                                         bg_lam, bg_wsum, bg_ssum)
        else:
            t_dev, t_edge = _predict_tail_vec(
                cst, lam_hat, est_bw, bg_lam, bg_wsum, bg_ssum,
                jnp.float64(slo_q), tail_method,
                _tail_grow_iters(slo_q, tail_method))
        # hysteresis compares against a PREVIOUS decision, which exists once
        # every cohort has decided at least once
        decided = _decide_vec(t_dev, t_edge, prev_choice, hysteresis, idx >= stagger)
        decide_now = cohort == jnp.mod(idx, stagger)
        choice = jnp.where(decide_now, decided, prev_choice).astype(jnp.int32)

        # -- the loop closes: decisions become next epoch's edge loads -----
        off = (choice[:, None] == jnp.arange(e_n)[None, :])
        own = jnp.where(off, lam_t[:, None], 0.0)
        local = jnp.sum(own, axis=0)
        endo_total = local if axis_name is None else jax.lax.psum(local, axis_name)
        report = endo_total[None, :] - own
        est_endo_next = jnp.where(
            first, report, bg_alpha * report + (1 - bg_alpha) * est_endo)

        out = (choice, endo_total, est_bw, lam_hat, est_endo, est_exo)
        return (est_bw, counts, est_endo_next, est_exo, choice), out

    init = (
        jnp.zeros(n),
        jnp.zeros((n, window)),
        jnp.zeros((n, e_n)),
        jnp.zeros(e_n),
        jnp.full(n, ON_DEVICE, dtype=jnp.int32),
    )
    inputs = (bw_true, lam_true, exo_true, n_req_all, jnp.arange(t_n))
    _, outs = jax.lax.scan(step, init, inputs)
    return outs


@partial(jax.jit, static_argnames=("window", "stagger", "slo_q", "tail_method"))
def _closed_loop_scan(cst, bw_true, lam_true, exo_true, n_req, *, window: int,
                      stagger: int, dt, bw_alpha, bg_alpha, hysteresis,
                      slo_q: float | None = None, tail_method: str = "asymptote"):
    """Decisions/estimates/loads of the adaptive policy over all T epochs —
    :func:`_scan_epochs` over the whole fleet as one block."""
    n = lam_true.shape[1]
    cohort = jnp.mod(jnp.arange(n), stagger)
    return _scan_epochs(
        cst, cst["lam_spec"], cohort, bw_true, lam_true, exo_true, n_req,
        window=window, stagger=stagger, dt=dt, bw_alpha=bw_alpha,
        bg_alpha=bg_alpha, hysteresis=hysteresis, slo_q=slo_q,
        tail_method=tail_method)


@partial(jax.jit,
         static_argnames=("window", "stagger", "shards", "slo_q", "tail_method"))
def _closed_loop_scan_blocked(cst, bw_true, lam_true, exo_true, n_req, *,
                              window: int, stagger: int, shards: int, dt,
                              bw_alpha, bg_alpha, hysteresis,
                              slo_q: float | None = None,
                              tail_method: str = "asymptote"):
    """Single-host sharded twin of :func:`_closed_loop_scan`: clients split
    into ``shards`` equal blocks, :func:`_scan_epochs` vmapped over the block
    axis with the endogenous total restored by ``psum`` over the vmap axis.
    Numerically identical math, the load sum merely re-associated — this is
    the fallback (and the exactness oracle) for the ``shard_map`` path when
    fewer than ``shards`` devices exist."""
    t_n, n = lam_true.shape
    nb = n // shards

    def blocks(a):  # (T, N, ...) -> (B, T, nb, ...) per-shard leading axis
        return jnp.moveaxis(a.reshape(t_n, shards, nb, *a.shape[2:]), 1, 0)

    cohort = jnp.mod(jnp.arange(n), stagger).reshape(shards, nb)
    lam_spec = cst["lam_spec"].reshape(shards, nb)
    run = partial(_scan_epochs, window=window, stagger=stagger, dt=dt,
                  bw_alpha=bw_alpha, bg_alpha=bg_alpha, hysteresis=hysteresis,
                  slo_q=slo_q, tail_method=tail_method, axis_name="shards")
    choice, endo_total, est_bw, lam_hat, est_endo, est_exo = jax.vmap(
        run, in_axes=(None, 0, 0, 0, 0, None, 0), axis_name="shards")(
        cst, lam_spec, cohort, blocks(bw_true), blocks(lam_true), exo_true,
        blocks(n_req))

    def merge(a):  # (B, T, nb, ...) -> (T, N, ...)
        return jnp.moveaxis(a, 0, 1).reshape(t_n, n, *a.shape[3:])

    # psum makes the shared outputs identical on every shard — keep shard 0
    return (merge(choice), endo_total[0], merge(est_bw), merge(lam_hat),
            merge(est_endo), est_exo[0])


def _closed_loop_scan_shardmap(cst, bw_true, lam_true, exo_true, n_req, *,
                               window: int, stagger: int, shards: int, dt,
                               bw_alpha, bg_alpha, hysteresis,
                               slo_q: float | None = None,
                               tail_method: str = "asymptote"):
    """Multi-device sharded twin of :func:`_closed_loop_scan`: client blocks
    placed one per device via ``shard_map``, with the endogenous-load total
    as the only cross-device collective per epoch. Same math as
    ``_closed_loop_scan_blocked`` (its single-host oracle) — the decision
    loop is embarrassingly parallel in clients given lagged load reports."""
    from jax.sharding import Mesh, PartitionSpec as P

    n = lam_true.shape[1]
    mesh = Mesh(np.array(jax.devices()[:shards]), ("shards",))
    cohort = jnp.mod(jnp.arange(n), stagger)
    run = partial(_scan_epochs, window=window, stagger=stagger, dt=dt,
                  bw_alpha=bw_alpha, bg_alpha=bg_alpha, hysteresis=hysteresis,
                  slo_q=slo_q, tail_method=tail_method, axis_name="shards")
    cols = P(None, "shards")
    fn = jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(), P("shards"), P("shards"), cols, cols, P(), cols),
        out_specs=(cols, P(), cols, cols, P(None, "shards", None), P()),
        check_vma=False)
    return jax.jit(fn)(cst, cst["lam_spec"], cohort, bw_true, lam_true,
                       exo_true, n_req)


def _pad_clients(cst, bw_true, lam_true, n_req, pad: int):
    """Append ``pad`` inert dummy clients so the client axis splits evenly
    into shards. A dummy has TRUE arrival rate 0 — zero pre-drawn counts and
    zero contribution to every endogenous sum — so its presence is exact, not
    approximate; its spec-rate fallback is a harmless 1 rps (its decisions
    are computed and discarded). Padding happens AFTER Poisson sampling, so
    real clients' draws are untouched."""
    if pad == 0:
        return cst, bw_true, lam_true, n_req
    cst = dict(cst)
    cst["lam_spec"] = jnp.concatenate([cst["lam_spec"], jnp.ones(pad)])

    def padcols(a, fill):
        return jnp.concatenate(
            [a, jnp.full((a.shape[0], pad), fill, dtype=a.dtype)], axis=1)

    return cst, padcols(bw_true, 1.0), padcols(lam_true, 0.0), padcols(n_req, 0.0)


# ---------------------------------------------------------------------------
# true-condition scoring: the analytic_vec closed forms over all T*N epochs
# ---------------------------------------------------------------------------


def _truth_batch(cst, lam_true, bw_true, exo_true, choices):
    """The (T*N)-row ScenarioBatch-style column dict of every client-epoch
    under the TRUE conditions — the single construction both the mean and the
    SLO-quantile scoring tables consume, with the endogenous aggregate minus
    the client's own contribution at its chosen edge as background."""
    t_n, n = lam_true.shape
    e_n = exo_true.shape[1]
    off = (choices[..., None] == jnp.arange(e_n)[None, None, :])
    own = jnp.where(off, lam_true[..., None], 0.0)
    endo_total = jnp.sum(own, axis=1)  # (T, E)
    bg_other = endo_total[:, None, :] - own  # (T, N, E)
    bg_lam, bg_wsum, bg_ssum = _bg_moments(cst, bg_other, exo_true[:, None, :])
    b = t_n * n
    ones = jnp.ones((b, e_n))
    c = {
        "lam": lam_true.reshape(b),
        "req_bytes": jnp.full(b, cst["req_bytes"]),
        "res_bytes": jnp.full(b, cst["res_bytes"]),
        "bandwidth_Bps": bw_true.reshape(b),
        "return_results": jnp.full(b, cst["return_results"], dtype=bool),
        "dev_s": jnp.full(b, cst["dev_s"]),
        "dev_k": jnp.full(b, cst["dev_k"]),
        "dev_var": jnp.full(b, cst["dev_var"]),
        "dev_model": jnp.full(b, cst["dev_model"], dtype=jnp.int8),
        "edge_mask": jnp.ones((b, e_n), dtype=bool),
        "edge_s": ones * cst["edge_s"],
        "edge_k": ones * cst["edge_k"],
        "edge_var": ones * cst["edge_var"],
        "edge_model": (ones * cst["edge_model"]).astype(jnp.int8),
        "edge_bw": ones * cst["edge_bw"],
        "bg_lam": bg_lam.reshape(b, e_n),
        "bg_wsum": bg_wsum.reshape(b, e_n),
        "bg_ssum": bg_ssum.reshape(b, e_n),
    }
    return c, endo_total


@jax.jit
def _latency_tables_jit(cst, lam_true, bw_true, exo_true, choices):
    """(T, N) t_dev and (T, N, E) t_edge expected latency under the TRUE
    conditions — one batched ``_edge_latency_vec`` call over T*N rows."""
    t_n, n = lam_true.shape
    e_n = exo_true.shape[1]
    c, endo_total = _truth_batch(cst, lam_true, bw_true, exo_true, choices)
    t_dev = _device_latency_vec(c).reshape(t_n, n)
    t_edge = _edge_latency_vec(c).reshape(t_n, n, e_n)
    return t_dev, t_edge, endo_total


@partial(jax.jit, static_argnames=("tail_method", "grow_iters"))
def _latency_tables_tail_jit(cst, lam_true, bw_true, exo_true, choices, q,
                             *, tail_method: str, grow_iters: int | None = None):
    """The q-quantile twin of :func:`_latency_tables_jit` (analytic
    semantics: mixture mean as s_edge, exactly like ``_edge_tail_vec``)."""
    t_n, n = lam_true.shape
    e_n = exo_true.shape[1]
    c, endo_total = _truth_batch(cst, lam_true, bw_true, exo_true, choices)
    t_dev = _device_tail_vec(c, q, tail_method, grow_iters).reshape(t_n, n)
    t_edge = _edge_tail_vec(c, q, tail_method, grow_iters).reshape(t_n, n, e_n)
    return t_dev, t_edge, endo_total


def _score_assignment(
    cst_j, lam_true, bw_true, exo_true, choices,
    slo_quantile: float | None = None, tail_method: str = "asymptote",
) -> tuple[np.ndarray, np.ndarray]:
    """True-condition latency (mean, or the q-quantile when ``slo_quantile``
    is set) of every (epoch, client) under ``choices``."""
    args = (cst_j, jnp.asarray(lam_true), jnp.asarray(bw_true),
            jnp.asarray(exo_true), jnp.asarray(choices, dtype=jnp.int32))
    if slo_quantile is None:
        t_dev, t_edge, endo_total = _latency_tables_jit(*args)
    else:
        t_dev, t_edge, endo_total = _latency_tables_tail_jit(
            *args, jnp.float64(slo_quantile), tail_method=tail_method,
            grow_iters=_tail_grow_iters(slo_quantile, tail_method))
    stacked = jnp.concatenate([t_dev[:, :, None], t_edge], axis=2)
    idx = (jnp.asarray(choices, dtype=jnp.int32) + 1)[..., None]
    lat = jnp.take_along_axis(stacked, idx, axis=2)[..., 0]
    return np.asarray(lat), np.asarray(endo_total)


# ---------------------------------------------------------------------------
# results
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ClusterPolicyResult:
    """One policy's scored trajectory through the cluster replay."""

    name: str
    latencies_s: np.ndarray  # (T, N) true-condition latency per client-epoch
    choices: np.ndarray  # (T, N) per-epoch target (ON_DEVICE for local)
    edge_loads: np.ndarray  # (T, E) endogenous offloaded rate per edge
    saturated_epochs: int  # client-epochs clamped at the saturation penalty

    @property
    def mean_latency_s(self) -> float:
        return float(np.mean(self.latencies_s))

    @property
    def per_client_mean_s(self) -> np.ndarray:
        return self.latencies_s.mean(axis=0)

    @property
    def switches(self) -> int:
        """Total decision changes across all clients (flapping metric)."""
        return int(np.sum(self.choices[1:] != self.choices[:-1]))

    @property
    def offload_frac(self) -> float:
        return float(np.mean(self.choices >= 0))


@dataclass(frozen=True)
class ClusterResult:
    """Closed-loop replay outcome: per-policy scores + estimator trajectories."""

    spec: ClusterSpec
    traces: TraceBatch
    policies: dict[str, ClusterPolicyResult]
    est_bandwidth_Bps: np.ndarray  # (T, N) EWMA view the managers acted on
    est_arrival_rate: np.ndarray  # (T, N) sliding-window view
    est_endo_rate: np.ndarray  # (T, N, E) estimated other-client load per edge
    est_exo_rate: np.ndarray  # (T, E) estimated exogenous background

    @property
    def client_epochs(self) -> int:
        return int(self.traces.n_epochs * self.traces.n_clients)

    @property
    def adaptive_wins(self) -> bool:
        """§6 criterion: adaptive mean <= every static policy's mean."""
        a = self.policies["adaptive"].mean_latency_s
        return all(
            a <= p.mean_latency_s for n, p in self.policies.items() if n != "adaptive"
        )


def simulate_cluster(
    spec: ClusterSpec,
    traces: TraceBatch | Trace,
    *,
    policies: Sequence[str] = ("adaptive", "on_device", "edge[0]"),
    seed: int = 0,
    bw_alpha: float = 0.5,
    bg_alpha: float = 0.5,
    rate_window_epochs: int = 5,
    saturation_penalty_s: float = 30.0,
    hysteresis: float = 0.0,
    stagger: int = 1,
    shards: int = 1,
    slo_quantile: float | None = None,
    tail_method: str = "asymptote",
    tracer=None,
) -> ClusterResult:
    """Drive N clients through the trace batch with the loop closed.

    ``slo_quantile`` switches decisions AND true-condition scoring from
    expected latencies to the q-quantile of each path's closed-form sojourn
    distribution (:mod:`repro.fleet.tail_vec`, ``tail_method="asymptote"`` by
    default — the cheap dominant-singularity form that vectorises inside the
    ``lax.scan``).

    The adaptive policy runs the vectorized Algorithm-1 path per client per
    epoch inside one ``lax.scan`` (decisions feed the loads the estimators
    see next epoch); every policy — adaptive and the all-clients statics —
    is then scored under the TRUE conditions with one batched
    ``analytic_vec`` call over all T*N client-epochs, with the same bounded
    saturation penalty the scalar replay applies. ``stagger`` spreads
    clients over k staggered decision cohorts (see ``_scan_epochs``);
    leave it at 1 for fully synchronous control.

    ``shards`` splits the client axis into that many blocks for the decision
    scan — one block per device via ``shard_map`` when enough JAX devices
    exist, otherwise a vmapped single-host blocking. Decisions within an
    epoch depend only on lagged load reports, so the split is EXACT: the
    one cross-client quantity (the endogenous per-edge load total) is
    restored by a per-epoch ``psum``, and Poisson arrival counts are drawn
    once, before blocking, from the same seed-keyed chain the unsharded scan
    uses. Results match ``shards=1`` decision-for-decision (float outputs to
    reduction-reassociation tolerance). Clients are padded with inert
    zero-rate dummies when ``shards`` does not divide N."""
    if isinstance(traces, Trace):
        traces = TraceBatch.from_trace(traces, spec.n_clients)
    if traces.n_clients != spec.n_clients:
        raise ScenarioError(
            "traces", f"trace batch has {traces.n_clients} client columns but "
            f"the cluster has {spec.n_clients} clients")
    if traces.n_edges not in (0, spec.n_edges):
        raise ScenarioError(
            "traces", f"trace batch has {traces.n_edges} edge columns but the "
            f"cluster has {spec.n_edges} edges")
    if rate_window_epochs < 1:
        raise ValueError("rate_window_epochs must be >= 1")
    if not 1 <= stagger <= spec.n_clients:
        raise ValueError(f"stagger must be in [1, n_clients], got {stagger}")
    if not 1 <= shards <= spec.n_clients:
        raise ValueError(f"shards must be in [1, n_clients], got {shards}")
    if slo_quantile is not None and not 0.0 < slo_quantile < 1.0:
        raise ValueError(f"slo_quantile must be in (0, 1), got {slo_quantile}")
    if slo_quantile is not None:
        tail_method = resolve_tail_method(slo_quantile, tail_method)

    cst = _spec_arrays(spec)
    t_n, e_n = traces.n_epochs, spec.n_edges
    # a trace without edge columns means "no churn", not "no tenants" (cf.
    # replay): the spec's declared exogenous rates hold every epoch
    exo_true = traces.edge_bg_rate if traces.n_edges else \
        np.broadcast_to(cst["exo_rate"], (t_n, e_n)).copy()

    static_targets = {
        name: parse_policy(name, e_n) for name in policies if name != "adaptive"
    }

    with x64():
        cst_j = _as_jnp(cst)
        bw_j = jnp.asarray(traces.bandwidth_Bps)
        lam_j = jnp.asarray(traces.arrival_rate)
        exo_j = jnp.asarray(exo_true)

        results: dict[str, ClusterPolicyResult] = {}
        est_bw = est_lam = est_endo = est_exo = None
        if "adaptive" in policies:
            n_req = _poisson_counts(seed, lam_j, jnp.float64(traces.epoch_s))
            scan_kw = dict(
                window=int(rate_window_epochs),
                stagger=int(stagger),
                dt=jnp.float64(traces.epoch_s),
                bw_alpha=jnp.float64(bw_alpha),
                bg_alpha=jnp.float64(bg_alpha),
                hysteresis=jnp.float64(hysteresis),
                slo_q=slo_quantile,
                tail_method=tail_method,
            )
            if shards == 1:
                outs = _closed_loop_scan(cst_j, bw_j, lam_j, exo_j, n_req,
                                         **scan_kw)
            else:
                pad = (-spec.n_clients) % shards
                cst_p, bw_p, lam_p, nreq_p = _pad_clients(
                    cst_j, bw_j, lam_j, n_req, pad)
                scan = (_closed_loop_scan_shardmap
                        if len(jax.devices()) >= shards
                        else _closed_loop_scan_blocked)
                outs = scan(cst_p, bw_p, lam_p, exo_j, nreq_p,
                            shards=int(shards), **scan_kw)
                if pad:
                    keep = spec.n_clients
                    outs = (outs[0][:, :keep], outs[1], outs[2][:, :keep],
                            outs[3][:, :keep], outs[4][:, :keep], outs[5])
            choice, _loads, bw_e, lam_e, endo_e, exo_e = outs
            choices = np.asarray(choice)
            est_bw, est_lam = np.asarray(bw_e), np.asarray(lam_e)
            est_endo, est_exo = np.asarray(endo_e), np.asarray(exo_e)
            lat, loads = _score_assignment(cst_j, lam_j, bw_j, exo_j, choices,
                                           slo_quantile, tail_method)
            lat, saturated = clamp_saturation(lat, saturation_penalty_s)
            results["adaptive"] = ClusterPolicyResult(
                "adaptive", lat, choices, loads, saturated)
            if tracer is not None:
                # per-epoch fleet-aggregate decide spans (the scan itself is
                # jitted — spans are reconstructed from its outputs, stamped
                # on the trace clock)
                dt = float(traces.epoch_s)
                for t in range(t_n):
                    offloaded = int(np.sum(choices[t] >= 0))
                    tracer.span(
                        t=t * dt, dur=dt, name="decide", cat="decide",
                        track="cluster", epoch=t, offloaded=offloaded,
                        on_device=int(choices.shape[1] - offloaded),
                        mean_latency_s=float(np.mean(lat[t])))

        for name, tgt in static_targets.items():
            choices = np.full((t_n, spec.n_clients), tgt, dtype=np.int32)
            lat, loads = _score_assignment(cst_j, lam_j, bw_j, exo_j, choices,
                                           slo_quantile, tail_method)
            lat, saturated = clamp_saturation(lat, saturation_penalty_s)
            results[name] = ClusterPolicyResult(name, lat, choices, loads, saturated)

    t_shape = (t_n, spec.n_clients)
    return ClusterResult(
        spec=spec,
        traces=traces,
        policies=results,
        est_bandwidth_Bps=est_bw if est_bw is not None else np.zeros(t_shape),
        est_arrival_rate=est_lam if est_lam is not None else np.zeros(t_shape),
        est_endo_rate=est_endo if est_endo is not None else np.zeros((*t_shape, e_n)),
        est_exo_rate=est_exo if est_exo is not None else np.zeros((t_n, e_n)),
    )


# ---------------------------------------------------------------------------
# fixed-point equilibrium under constant conditions
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Equilibrium:
    """A fixed point of the decision -> load -> decision map.

    Carries the operating conditions it was solved under (per-client arrival
    rates and bandwidths, exogenous edge rates) so downstream consumers —
    the event-driven cross-check above all — evaluate exactly the system the
    fixed point belongs to, overrides included."""

    choices: np.ndarray  # (N,) per-client target at the fixed point
    iterations: int  # best-response evaluations performed
    converged: bool
    oscillation: bool  # True when damped switching had to engage
    latency_s: np.ndarray  # (N,) analytic per-client latency at the fixed point
    edge_loads: np.ndarray  # (E,) endogenous offloaded rate per edge
    rho_edges: np.ndarray  # (E,) processing utilization incl. exogenous load
    arrival_rates: np.ndarray  # (N,) the rates the fixed point was solved at
    bandwidth_Bps: np.ndarray  # (N,) per-client shared-path bandwidth used
    exo_rates: np.ndarray  # (E,) exogenous background rates used

    @property
    def mean_latency_s(self) -> float:
        return float(np.mean(self.latency_s))

    @property
    def max_latency_s(self) -> float:
        """Worst per-client latency at the fixed point — the number an SLO
        constrains. With ``slo_quantile`` set at solve time, ``latency_s``
        already holds per-client quantiles, so this is the fleet-wide
        worst-client q-quantile."""
        return float(np.max(self.latency_s))

    def meets_slo(self, slo_s: float) -> bool:
        """Feasibility predicate the provisioning solver bisects over: a
        converged fixed point whose worst client is within the budget.
        Non-convergence counts as infeasible — an oscillating assignment has
        no per-client latency anyone can promise."""
        return bool(self.converged and self.max_latency_s <= slo_s)

    def counts(self) -> dict[str, int]:
        """Clients per target, keyed like ``Decision.target_name``."""
        out = {"on_device": int(np.sum(self.choices == ON_DEVICE))}
        for j in range(len(self.edge_loads)):
            out[f"edge[{j}]"] = int(np.sum(self.choices == j))
        return out


def _equilibrium_tables(cst_j, lam, bw, exo, choices,
                        slo_quantile=None, tail_method="asymptote"):
    args = (cst_j, jnp.asarray(lam[None, :]), jnp.asarray(bw[None, :]),
            jnp.asarray(exo[None, :]), jnp.asarray(choices[None, :], dtype=jnp.int32))
    if slo_quantile is None:
        t_dev, t_edge, endo = _latency_tables_jit(*args)
    else:
        t_dev, t_edge, endo = _latency_tables_tail_jit(
            *args, jnp.float64(slo_quantile), tail_method=tail_method,
            grow_iters=_tail_grow_iters(slo_quantile, tail_method))
    return np.asarray(t_dev)[0], np.asarray(t_edge)[0], np.asarray(endo)[0]


def solve_equilibrium(
    spec: ClusterSpec,
    *,
    bandwidth_Bps: float | np.ndarray | None = None,
    arrival_rates: np.ndarray | None = None,
    exo_rates: np.ndarray | None = None,
    max_iter: int = 20,
    slo_quantile: float | None = None,
    tail_method: str = "asymptote",
) -> Equilibrium:
    """Iterate decisions -> loads to a fixed point under constant conditions.

    With ``slo_quantile`` set, clients best-respond on q-quantiles instead of
    means (an SLO-aware congestion game) and ``latency_s`` reports the
    per-client quantile at the fixed point.

    Clients best-respond synchronously with perfect information (the true
    closed forms, no estimator lag). When the decision vector revisits a
    previous state — the classic cycle where a crowd stampedes onto the
    cheapest edge, saturates it, and stampedes off again — the solver
    switches to *damped* tie-breaking: one sequential best-response sweep
    per iteration (clients move one at a time in index order against the
    live assignment, argmin ties broken deterministically toward on-device /
    the lowest edge index). Each damped move strictly lowers the mover's
    latency given the others, so the dynamics descend a congestion potential
    instead of oscillating; a sweep with no moves is the fixed point."""
    if slo_quantile is not None and not 0.0 < slo_quantile < 1.0:
        raise ValueError(f"slo_quantile must be in (0, 1), got {slo_quantile}")
    if slo_quantile is not None:
        tail_method = resolve_tail_method(slo_quantile, tail_method)
    n, e_n = spec.n_clients, spec.n_edges
    cst = _spec_arrays(spec)
    lam = np.asarray(arrival_rates, dtype=np.float64) if arrival_rates is not None \
        else spec.arrival_rates()
    if lam.shape != (n,):
        raise ScenarioError("arrival_rates", f"expected shape ({n},), got {lam.shape}")
    bw_default = float(np.asarray(spec.base.network.bandwidth_Bps))
    bw = np.broadcast_to(
        np.asarray(bw_default if bandwidth_Bps is None else bandwidth_Bps,
                   dtype=np.float64), (n,)).copy()
    exo = np.asarray(exo_rates, dtype=np.float64) if exo_rates is not None \
        else cst["exo_rate"].copy()
    if exo.shape != (e_n,):
        raise ScenarioError("exo_rates", f"expected shape ({e_n},), got {exo.shape}")

    with x64():
        cst_j = _as_jnp(cst)
        choices = np.full(n, ON_DEVICE, dtype=np.int32)
        seen = {choices.tobytes()}
        damped = False
        converged = False
        iterations = 0

        def tables(ch):
            t_dev, t_edge, _ = _equilibrium_tables(cst_j, lam, bw, exo, ch,
                                                   slo_quantile, tail_method)
            return np.concatenate([t_dev[:, None], t_edge], axis=1)

        stacked = tables(choices)
        while iterations < max_iter:
            iterations += 1
            if not damped:
                best = (np.argmin(stacked, axis=1) - 1).astype(np.int32)
                if np.array_equal(best, choices):
                    converged = True
                    break
                if best.tobytes() in seen:
                    damped = True  # oscillation: fall back to damped sweeps
                    continue
                seen.add(best.tobytes())
                choices = best
                stacked = tables(choices)
            else:
                # one sequential sweep: each client best-responds against the
                # LIVE assignment, so no two clients can stampede together
                moved = False
                for i in range(n):
                    b_i = int(np.argmin(stacked[i])) - 1
                    if b_i != choices[i]:
                        choices[i] = b_i
                        moved = True
                        stacked = tables(choices)
                if not moved:
                    converged = True
                    break

        # every exit path above leaves `stacked` consistent with `choices`
        latency = stacked[np.arange(n), choices + 1]
        off = choices[:, None] == np.arange(e_n)[None, :]
        endo = np.where(off, lam[:, None], 0.0).sum(axis=0)

        # processing utilization of the realized aggregate mixture per edge
        rates = np.concatenate([np.where(off, lam[:, None], 0.0), exo[None, :]], axis=0)
        means = np.concatenate([
            np.broadcast_to(cst["endo_mean"], (n, e_n)), cst["exo_mean"][None, :]
        ], axis=0)
        variances = np.concatenate([
            np.broadcast_to(cst["endo_var"], (n, e_n)), cst["exo_var"][None, :]
        ], axis=0)
        lam_tot, mean_mix, _ = mixture_moments(rates.T, means.T, variances.T)
        rho = lam_tot * mean_mix / cst["edge_k"]

    return Equilibrium(
        choices=choices,
        iterations=iterations,
        converged=converged,
        oscillation=damped,
        latency_s=latency,
        edge_loads=endo,
        rho_edges=rho,
        arrival_rates=lam,
        bandwidth_Bps=bw,
        exo_rates=exo,
    )


# ---------------------------------------------------------------------------
# event-driven cross-check (the PR 3 differential pattern, closed-loop)
# ---------------------------------------------------------------------------


def induced_scenario(
    spec: ClusterSpec,
    choices: np.ndarray,
    i: int,
    *,
    bandwidth_Bps: float | None = None,
    arrival_rates: np.ndarray | None = None,
    exo_rates: np.ndarray | None = None,
    allow_unstable: bool = False,
    name: str | None = None,
) -> Scenario:
    """Client ``i``'s open-loop equivalent of a cluster assignment.

    The other clients' realized offload streams become explicit background
    ``TenantStream``s on their chosen edges — one stream PER client, not one
    pre-aggregated lump, because each client owns its device NIC: lumping 47
    two-rps uplinks into one 94-rps stream would saturate the simulator's
    single per-stream NIC and silently throttle + smooth the load the edge
    sees (the analytic mixture is identical either way; the event-driven
    arrival process is not). The induced spec then runs through every
    open-loop path unchanged: ``analytic()``, ``simulate()``, the validation
    corpus. This is the bridge the closed-loop cross-check and the corpus's
    cluster regime are built on.

    ``exo_rates`` overrides the exogenous background: the spec's declared
    per-edge streams are replaced by one template stream at the given rate
    (the same re-expansion a churned trace gets). ``None`` keeps the spec's
    streams verbatim — preferable when they apply, because the simulator
    gives every background stream its own device NIC."""
    choices = np.asarray(choices, dtype=np.int64).reshape(spec.n_clients)
    lam = np.asarray(arrival_rates, dtype=np.float64) if arrival_rates is not None \
        else spec.arrival_rates()
    base = spec.base
    cst = _spec_arrays(spec)

    edges = []
    for j, e in enumerate(base.edges):
        if exo_rates is None:
            bg = e.background
        elif exo_rates[j] > 0:
            bg = (TenantStream(
                arrival_rate=float(exo_rates[j]),
                service_mean_s=float(cst["exo_mean"][j]),
                service_var=float(cst["exo_var"][j]),
                name="exogenous",
            ),)
        else:
            bg = ()
        for c in range(spec.n_clients):
            if c != i and choices[c] == j:
                bg = bg + (TenantStream(
                    arrival_rate=float(lam[c]),
                    service_mean_s=float(cst["endo_mean"][j]),
                    service_var=float(cst["endo_var"][j]),
                    name=f"cluster-client[{c}]",
                ),)
        edges.append(replace(e, background=bg))

    scn = Scenario(
        workload=replace(base.workload, arrival_rate=float(lam[i])),
        device=base.device,
        network=base.network if bandwidth_Bps is None
        else NetworkPath(float(bandwidth_Bps)),
        edges=tuple(edges),
        return_results=base.return_results,
        allow_unstable=allow_unstable,
        name=name or f"{spec.name}-client{i}",
    )
    return scn


def cross_check_equilibrium(
    spec: ClusterSpec,
    eq: Equilibrium,
    *,
    n: int = 120_000,
    seed: int = 0,
    rho_gate: float = 0.9,
) -> dict:
    """Validate the closed-loop analytic means against event-driven simulation.

    The operating point — per-client arrival rates and bandwidths, exogenous
    edge rates — comes from the :class:`Equilibrium` itself, so overrides
    passed to :func:`solve_equilibrium` are honoured and the simulated system
    is exactly the one the fixed point belongs to. Clients are grouped by
    (target, arrival rate, bandwidth) — within a group every client is
    statistically identical, so one representative simulation per group
    covers the fleet. On-device groups run through the batched Lindley
    simulator (``simulate_fleet``); offloading groups run the scalar
    shared-station multi-tenant simulator on the representative's *induced*
    scenario (the other offloaders as background streams), observing the
    representative's own stream. Groups whose bottleneck utilization exceeds
    ``rho_gate`` are reported but not gated, exactly like the PR 3 corpus."""
    lam = eq.arrival_rates
    # spec-default exogenous rates keep the spec's own per-stream background
    # (each stream gets its own NIC in the sim); overridden rates are
    # re-expanded through the template
    exo = None if np.array_equal(eq.exo_rates, _spec_arrays(spec)["exo_rate"]) \
        else eq.exo_rates
    choices = eq.choices

    def induced(i: int) -> Scenario:
        return induced_scenario(
            spec, choices, i,
            bandwidth_Bps=float(eq.bandwidth_Bps[i]),
            arrival_rates=lam,
            exo_rates=exo,
            allow_unstable=True,
        )

    groups: dict[tuple[int, float, float], list[int]] = {}
    for i in range(spec.n_clients):
        groups.setdefault(
            (int(choices[i]), float(lam[i]), float(eq.bandwidth_Bps[i])), []
        ).append(i)

    reports = []
    dev_members: list[tuple[tuple[int, float, float], int]] = []
    for key, members in groups.items():
        if key[0] == ON_DEVICE:
            dev_members.append((key, members[0]))

    # -- on-device groups: one batched Lindley launch -------------------------
    dev_means: dict[tuple[int, float, float], float] = {}
    if dev_members:
        scns = [induced(i) for _, i in dev_members]
        batch = ScenarioBatch.from_scenarios(scns)
        res = simulate_fleet(batch, "on_device", n=n, seed=seed)
        steady = res.latencies[:, steady_slice(n)]
        for row, (key, _i) in enumerate(dev_members):
            dev_means[key] = float(steady[row].mean())

    for key, members in sorted(groups.items()):
        tgt, lam_i, _bw_i = key
        rep = members[0]
        scn = induced(rep)
        strategy = "on_device" if tgt == ON_DEVICE else f"edge[{tgt}]"
        pred = float(np.asarray(scalar_analytic(scn).totals()[strategy]))
        if tgt == ON_DEVICE:
            rho = lam_i * scn.device.service_time_s / scn.device.parallelism_k
            sim_mean = dev_means[key]
        else:
            e = scn.edges[tgt]
            b = float(np.asarray(scn.network_for(e).bandwidth_Bps))
            agg = e.aggregate(scn.workload)
            rhos = [lam_i * scn.workload.req_bytes / b,
                    agg.arrival_rate * agg.service_mean_s / e.tier.parallelism_k]
            if scn.return_results and scn.workload.res_bytes > 0:
                rhos.append(agg.arrival_rate * scn.workload.res_bytes / b)
            rho = float(max(rhos))
            res = scn.simulate(strategy, n=n, seed=seed + rep)
            sim_mean = res.stream_mean(0) if res.stream_ids is not None else res.mean
        err_pct = abs(pred - sim_mean) / sim_mean * 100.0
        reports.append({
            "target": strategy,
            "n_clients": len(members),
            "arrival_rate": lam_i,
            "rho": rho,
            "analytic_s": pred,
            "sim_mean_s": sim_mean,
            "mape_pct": err_pct,
            "gated": bool(rho <= rho_gate),
        })

    gated = [r["mape_pct"] for r in reports if r["gated"]]
    return {
        "groups": reports,
        "n_groups": len(reports),
        "gated_mean_mape_pct": float(np.mean(gated)) if gated else None,
        "gated_max_mape_pct": float(np.max(gated)) if gated else None,
        "rho_gate": rho_gate,
        "config": {"n": n, "seed": seed},
    }
