"""jit+vmap closed forms over a :class:`ScenarioBatch`.

Vectorized transcription of exactly the scalar path in
``repro.core.latency`` / ``repro.core.multitenant`` / ``repro.core.scenario``:
M/D/1, M/M/1 and M/G/1 (P-K) waits with the paper's k*mu aggregation, the
Eq. 1/2 end-to-end compositions, the §3.4 multi-tenant mixture (own stream
folded into the stored background sums at evaluation time), and batched
bisection for crossover points. One jitted call evaluates the whole fleet —
millions of scenarios per second on a laptop CPU, every row bit-comparable
(<= 1e-9 relative) to ``scenario.analytic()`` on the same spec.

All math runs in float64 inside a scoped ``repro.jaxenv.x64()`` context so
the closed forms keep numpy-double semantics without flipping the
process-global x64 switch out from under the float32 model/kernel stack.
Unstable operating points yield ``inf``, exactly as the kernel layer does.

An exact Erlang-C M/M/k wait (``mmk_wait_erlang_vec``) rides along as the
vectorized counterpart of ``repro.core.queueing.mmk_wait_erlang`` — the test
oracle the paper's k*mu aggregation is scored against, now batched.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.jaxenv import x64

from .batch import ScenarioBatch

__all__ = [
    "FleetPrediction",
    "FleetCrossover",
    "fleet_analytic",
    "fleet_crossover",
    "mm1_wait_vec",
    "md1_wait_vec",
    "mg1_wait_vec",
    "mmk_wait_erlang_vec",
]

_INF = jnp.inf


def _stable_where(lam, effective_mu, value):
    """inf wherever the queue is unstable — mirrors latency._stable_where."""
    ok = (lam < effective_mu) & (effective_mu > 0) & (lam >= 0)
    return jnp.where(ok, value, _INF)


def mm1_wait_vec(lam, mu):
    """Paper Eq. 7: E[w] = 1/(mu - lam) - 1/mu."""
    w = 1.0 / (mu - lam) - 1.0 / mu
    return _stable_where(lam, mu, w)


def md1_wait_vec(lam, mu, k=1.0):
    """Paper Eq. 6: M/D/k via aggregated-rate M/D/1."""
    kmu = mu * k
    w = 0.5 * (1.0 / (kmu - lam) - 1.0 / kmu)
    return _stable_where(lam, kmu, w)


def mg1_wait_vec(lam, mu, var_s, k=1.0):
    """Paper Eq. 11: P-K M/G/1 wait with aggregated service rate k*mu."""
    kmu = mu * k
    rho = lam / kmu
    w = (rho + lam * kmu * var_s) / (2.0 * (kmu - lam))
    return _stable_where(lam, kmu, w)


def mmk_wait_erlang_vec(lam, mu, k, *, max_k: int = 64):
    """Exact M/M/k wait (Erlang C), batched over integer server counts.

    The per-row sum over n < k is evaluated as a masked sum to ``max_k``
    terms, so heterogeneous k across the batch stays one fused kernel.
    Runs in its own scoped float64 context (safe to call from numpy code;
    from inside an already-x64 trace the context is a no-op).
    """
    lam_np = np.asarray(lam, dtype=np.float64)
    mu_np = np.asarray(mu, dtype=np.float64)
    k_np = np.asarray(k, dtype=np.float64)
    if np.max(k_np) > max_k:
        raise ValueError(
            f"k={np.max(k_np)} exceeds max_k={max_k}; raise max_k or the "
            "truncated Erlang-B sum would be silently wrong")
    out_shape = np.broadcast_shapes(lam_np.shape, mu_np.shape, k_np.shape)
    with x64():
        out = _mmk_wait_erlang_impl(
            jnp.atleast_1d(jnp.asarray(lam_np)),
            jnp.atleast_1d(jnp.asarray(mu_np)),
            jnp.atleast_1d(jnp.asarray(k_np)),
            max_k=max_k,
        )
        return out.reshape(out_shape)


def _mmk_wait_erlang_impl(lam, mu, k, *, max_k: int):
    lam, mu, k = jnp.broadcast_arrays(lam, mu, k)
    a = lam / mu  # offered load in Erlangs
    rho = a / k
    n = jnp.arange(max_k, dtype=lam.dtype)
    log_n = jnp.log(jnp.maximum(n, 1.0))
    log_fact = jnp.cumsum(log_n)  # log(n!) since log(0!) = log(1) = 0
    # sum_{n<k} a^n/n!, a^k/k! — in log space for numeric range
    log_a = jnp.log(a)
    log_terms = n * log_a[..., None] - log_fact[None, :]
    mask = n < k[..., None]
    summation = jnp.sum(jnp.where(mask, jnp.exp(log_terms), 0.0), axis=-1)
    log_fact_km1 = jnp.sum(jnp.where(mask, log_n[None, :], 0.0), axis=-1)  # log((k-1)!)
    last = jnp.exp(k * log_a - (log_fact_km1 + jnp.log(k))) / (1.0 - rho)
    p_wait = last / (summation + last)
    w = jnp.where(lam == 0.0, 0.0, p_wait / (k * mu - lam))
    return _stable_where(lam, k * mu, w)


def _proc_wait_vec(model, lam, s, var, k):
    """Processing-queue wait, dispatching on the MODEL_CODES integer —
    the vectorized twin of ``latency.proc_wait``."""
    mu = 1.0 / s
    w_det = md1_wait_vec(lam, mu, k)
    w_exp = mm1_wait_vec(lam, mu * k)
    w_gen = mg1_wait_vec(lam, mu, var, k)
    return jnp.where(model == 0, w_det, jnp.where(model == 1, w_exp, w_gen))


def _implied_var_vec(model, s, var):
    """Var[s] implied by the service model (scenario.implied_service_var)."""
    return jnp.where(model == 1, s * s, jnp.where(model == 2, var, 0.0))


def _edge_latency_vec(c):
    """(B, E) end-to-end offload latency per edge — Eq. 1, with the §3.4
    mixture re-parameterisation wherever an edge hosts background tenants."""
    lam = c["lam"][:, None]
    has_bg = c["bg_lam"] > 0.0

    # mixture moments of background + the scenario's own stream (exactly
    # aggregate_streams: weighted mean, law-of-total-variance second moment)
    own_var = _implied_var_vec(c["edge_model"], c["edge_s"], c["edge_var"])
    lam_tot = lam + c["bg_lam"]
    mean_mix = (lam * c["edge_s"] + c["bg_wsum"]) / lam_tot
    second_mix = (lam * (own_var + c["edge_s"] ** 2) + c["bg_ssum"]) / lam_tot
    var_mix = jnp.maximum(0.0, second_mix - mean_mix**2)

    # dedicated edge: dispatch on the tier's own model at the own rate;
    # multi-tenant edge: M/G/1 on the aggregate (Lemma 3.2), s_edge = mixture mean
    w_proc_own = _proc_wait_vec(c["edge_model"], lam, c["edge_s"], c["edge_var"], c["edge_k"])
    w_proc_mix = mg1_wait_vec(lam_tot, 1.0 / mean_mix, var_mix, c["edge_k"])
    w_proc = jnp.where(has_bg, w_proc_mix, w_proc_own)
    s_edge = jnp.where(has_bg, mean_mix, c["edge_s"])
    lam_edge = jnp.where(has_bg, lam_tot, lam)

    b = jnp.where(jnp.isnan(c["edge_bw"]), c["bandwidth_Bps"][:, None], c["edge_bw"])
    req = c["req_bytes"][:, None]
    res = c["res_bytes"][:, None]
    w_net_dev = mm1_wait_vec(lam, b / req)  # device NIC sees this stream only
    n_req = req / b
    ret = c["return_results"][:, None]
    w_net_edge = jnp.where(ret, mm1_wait_vec(lam_edge, b / res), 0.0)
    n_res = jnp.where(ret, res / b, 0.0)

    total = w_net_dev + n_req + w_proc + s_edge + w_net_edge + n_res
    return jnp.where(c["edge_mask"], total, _INF)


def _device_latency_vec(c):
    """(B,) on-device latency — Eq. 2."""
    w = _proc_wait_vec(c["dev_model"], c["lam"], c["dev_s"], c["dev_var"], c["dev_k"])
    return w + c["dev_s"]


@jax.jit
def _fleet_analytic_jit(c):
    t_dev = _device_latency_vec(c)
    t_edge = _edge_latency_vec(c)
    stacked = jnp.concatenate([t_dev[:, None], t_edge], axis=1)
    # first argmin => on-device wins ties, matching ScenarioPrediction.best_strategy
    best = jnp.argmin(stacked, axis=1) - 1
    return t_dev, t_edge, best


@dataclass(frozen=True)
class FleetPrediction:
    """Per-scenario closed-form latencies of one fleet evaluation.

    ``best_edge`` follows the manager's convention: -1 means on-device,
    j >= 0 means ``edge[j]`` (padded edges are inf and never win).
    """

    t_dev: np.ndarray  # (B,)
    t_edge: np.ndarray  # (B, E)
    best_edge: np.ndarray  # (B,) int

    @property
    def size(self) -> int:
        return int(self.t_dev.shape[0])

    @property
    def best_latency(self) -> np.ndarray:
        stacked = np.concatenate([self.t_dev[:, None], self.t_edge], axis=1)
        return stacked[np.arange(self.size), self.best_edge + 1]

    def strategy_names(self) -> list[str]:
        """Decision.target_name-style labels per scenario."""
        return [
            "on_device" if j < 0 else f"edge[{j}]" for j in self.best_edge.tolist()
        ]

    def totals(self, i: int) -> dict[str, float]:
        """Scenario i's totals keyed like ScenarioPrediction.totals()
        (padded edge slots report inf)."""
        out = {"on_device": float(self.t_dev[i])}
        for j in range(self.t_edge.shape[1]):
            out[f"edge[{j}]"] = float(self.t_edge[i, j])
        return out


def fleet_analytic(batch: ScenarioBatch) -> FleetPrediction:
    """Closed-form per-strategy latency of every scenario, one jitted call."""
    with x64():
        arrays = {k: jnp.asarray(v) for k, v in batch.arrays().items()}
        t_dev, t_edge, best = _fleet_analytic_jit(arrays)
        return FleetPrediction(
            t_dev=np.asarray(t_dev),
            t_edge=np.asarray(t_edge),
            best_edge=np.asarray(best),
        )


# ---------------------------------------------------------------------------
# batched crossover solving (bandwidth / arrival_rate axes)
# ---------------------------------------------------------------------------


def _diff_at(c, x, axis_code: int, edge: int):
    """T_edge[edge](x) - T_dev(x) with the axis value substituted per row."""
    if axis_code == 0:  # bandwidth
        c = dict(c, bandwidth_Bps=x)
        # a swept shared path overrides any per-edge bandwidth, matching the
        # scalar solvers which always sweep NetworkPath(b)
        c["edge_bw"] = jnp.full_like(c["edge_bw"], jnp.nan)
    else:  # arrival rate
        c = dict(c, lam=x)
    t_dev = _device_latency_vec(c)
    t_edge = _edge_latency_vec(c)
    return t_edge[:, edge] - t_dev


@partial(jax.jit, static_argnames=("axis_code", "edge", "samples", "iters", "linear"))
def _fleet_crossover_jit(
    c, lo, hi, *, axis_code: int, edge: int, samples: int, iters: int, linear: bool
):
    # per-row grid: geometric when the span exceeds two decades (mirrors
    # solve_crossover), linear otherwise — or forced linear for the arrival
    # axis, matching arrival_rate_crossovers' linspace scan
    t = jnp.linspace(0.0, 1.0, samples)
    geom = lo[:, None] * (hi / lo)[:, None] ** t[None, :]
    lin = lo[:, None] + (hi - lo)[:, None] * t[None, :]
    use_geom = (not linear) & (lo > 0) & (hi / lo > 100)
    xs = jnp.where(use_geom[:, None], geom, lin)

    vals = jax.vmap(
        lambda x: _diff_at(c, x, axis_code, edge), in_axes=1, out_axes=1
    )(xs)

    # scan for the first sign change between grid-ADJACENT finite samples.
    # A non-finite sample resets the pairing: pairing across an instability
    # pocket (a run of inf between opposite-sign finite regions) would send
    # the bisection into the non-finite region and report a bogus crossover
    # at a stability boundary — the same fix as solve_crossover's scan.
    b = lo.shape[0]

    def scan_step(carry, col):
        last_x, last_v, found, blo, bhi, bflo, wins = carry
        x_i, v_i = col
        fin = jnp.isfinite(v_i)
        pair = fin & jnp.isfinite(last_v)
        hit = pair & (((last_v > 0) != (v_i > 0)) | (last_v == 0.0))
        new = hit & ~found
        blo = jnp.where(new, last_x, blo)
        bhi = jnp.where(new, x_i, bhi)
        bflo = jnp.where(new, last_v, bflo)
        wins = jnp.where(new, v_i < 0, wins)
        found = found | hit
        last_x = x_i
        last_v = jnp.where(fin, v_i, jnp.nan)  # non-finite breaks adjacency
        return (last_x, last_v, found, blo, bhi, bflo, wins), None

    init = (
        jnp.zeros(b),
        jnp.full(b, jnp.nan),
        jnp.zeros(b, dtype=bool),
        jnp.zeros(b),
        jnp.zeros(b),
        jnp.zeros(b),
        jnp.zeros(b, dtype=bool),
    )
    (_, _, found, blo, bhi, bflo, wins), _ = jax.lax.scan(
        scan_step, init, (xs.T, vals.T)
    )

    exact = found & (bflo == 0.0)  # grid point landed on the root

    def bisect_step(_, carry):
        lo_b, hi_b, flo = carry
        mid = 0.5 * (lo_b + hi_b)
        fm = _diff_at(c, mid, axis_code, edge)
        same = (fm > 0) == (flo > 0)
        lo_b = jnp.where(same, mid, lo_b)
        flo = jnp.where(same, fm, flo)
        hi_b = jnp.where(same, hi_b, mid)
        return lo_b, hi_b, flo

    lo_b, hi_b, _ = jax.lax.fori_loop(0, iters, bisect_step, (blo, bhi, bflo))
    root = 0.5 * (lo_b + hi_b)
    value = jnp.where(found, jnp.where(exact, blo, root), jnp.nan)
    return value, wins, found


@dataclass(frozen=True)
class FleetCrossover:
    """Batched Crossover: nan value where no sign change exists in [lo, hi]."""

    value: np.ndarray  # (B,)
    offload_wins_above: np.ndarray  # (B,) bool, meaningful where found
    found: np.ndarray  # (B,) bool
    lo: np.ndarray
    hi: np.ndarray


def fleet_crossover(
    batch: ScenarioBatch,
    axis: str,
    *,
    edge: int = 0,
    lo=None,
    hi=None,
    samples: int | None = None,
    iters: int = 200,
) -> FleetCrossover:
    """Where does the preferred strategy flip, for every scenario at once?

    ``axis`` is ``"bandwidth"`` (default range 1e4..1e9 B/s, as
    ``bandwidth_crossover``) or ``"arrival_rate"`` (per-row upper bound just
    inside every queue's stability region, as ``arrival_rate_crossovers``;
    the first crossover is returned). Same grid-scan-then-bisect procedure as
    ``repro.core.crossover.solve_crossover``, batched.
    """
    if batch.max_edges == 0 or not 0 <= edge < batch.max_edges:
        raise ValueError(f"edge index {edge} out of range for batch with "
                         f"{batch.max_edges} edge slots")
    with x64():
        c = {k: jnp.asarray(v) for k, v in batch.arrays().items()}
        b = batch.size
        if axis == "bandwidth":
            axis_code = 0
            linear = False
            samples = 256 if samples is None else samples
            lo_arr = jnp.full(b, 1e4 if lo is None else lo, dtype=jnp.float64)
            hi_arr = jnp.full(b, 1e9 if hi is None else hi, dtype=jnp.float64)
        elif axis == "arrival_rate":
            axis_code = 1
            linear = True
            samples = 512 if samples is None else samples
            lo_arr = jnp.full(b, 0.01 if lo is None else lo, dtype=jnp.float64)
            if hi is None:
                # stay strictly inside every queue's stability region
                bw = jnp.where(
                    jnp.isnan(c["edge_bw"][:, edge]),
                    c["bandwidth_Bps"],
                    c["edge_bw"][:, edge],
                )
                caps_dev = c["dev_k"] / c["dev_s"]
                caps_req = bw / c["req_bytes"]
                has_bg = c["bg_lam"][:, edge] > 0
                caps_edge = c["edge_k"][:, edge] / c["edge_s"][:, edge]
                caps_res = bw / c["res_bytes"]
                cap_nobg = jnp.minimum(
                    jnp.minimum(caps_dev, caps_edge), jnp.minimum(caps_req, caps_res)
                )
                cap_bg = jnp.minimum(caps_dev, caps_req)
                hi_arr = 0.999 * jnp.where(has_bg, cap_bg, cap_nobg)
            else:
                hi_arr = jnp.full(b, hi, dtype=jnp.float64)
        else:
            raise ValueError(f"unknown axis {axis!r} (known: bandwidth, arrival_rate)")
        value, wins, found = _fleet_crossover_jit(
            c, lo_arr, hi_arr, axis_code=axis_code, edge=edge,
            samples=samples, iters=iters, linear=linear,
        )
        return FleetCrossover(
            value=np.asarray(value),
            offload_wins_above=np.asarray(wins),
            found=np.asarray(found),
            lo=np.asarray(lo_arr),
            hi=np.asarray(hi_arr),
        )
