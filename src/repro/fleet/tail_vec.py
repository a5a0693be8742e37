"""jit+vmap sojourn-time quantiles over a :class:`ScenarioBatch`.

Vectorized transcription of exactly the scalar tail layer in
:mod:`repro.core.tail`: the Pollaczek-Khinchine sojourn transform per station
(wait factor on the paper's k*mu aggregation, full service on top), the
Fig. 1 tandem composition under the independence approximation, Abate-Whitt
Euler inversion for the numeric CDF, and the dominant-singularity exponential
asymptote as the cheap method the closed-loop cluster paths use inside
``lax.scan``. One jitted call batches the q-quantile of every scenario —
``fleet_tail(batch, 0.99)`` is to ``Scenario.analytic_tail`` exactly what
``fleet_analytic`` is to ``Scenario.analytic()``, and a validation check pins
the two to <= 1e-6 relative agreement over the full golden corpus.

All math runs in float64 inside a scoped ``repro.jaxenv.x64()`` so the
global f32 model/kernel stack is untouched; the Euler contour is carried as
paired real/imaginary float64 arrays (no complex dtype reaches the compiler). Algorithmic constants (Euler A/N/M, bracket/bisection iteration
counts) are imported from the scalar module — the agreement gate depends on
both sides running the identical algorithm.

The exact euler inversion itself lives in :mod:`repro.fleet.euler_vec`
(q-derived growth schedule + safeguarded Newton on the free Abate-Whitt
density, static per-slot service-kind hints), which replays the scalar
search trajectory phase for phase — this module routes ``method="euler"``
there and keeps the asymptote path plus the ScenarioBatch-facing station
builders and the public ``fleet_tail`` entry point.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tail import (
    ETA_BISECT_ITERS,
    ETA_GROW_ITERS,
    GAMMA_DET_CV2,
    KIND_DET,
    KIND_EXP,
    KIND_GAMMA,
    euler_grow_iters,
    resolve_tail_method,
)
from repro.jaxenv import x64

from .analytic_vec import _implied_var_vec
from .batch import ScenarioBatch
from .euler_vec import quantile_euler_vec

__all__ = ["FleetTailPrediction", "fleet_tail", "sojourn_quantile_vec"]

_INF = jnp.inf
_TINY = 1e-300


# ---------------------------------------------------------------------------
# station-field containers: a dict of arrays, station axis LAST
# (lam, wkind, wmean, wvar, fkind, fmean, fvar) — repro.core.tail.Station,
# columnar
# ---------------------------------------------------------------------------


def _stack_stations(*stations) -> dict[str, jnp.ndarray]:
    """Stack per-station field dicts along a new trailing station axis."""
    keys = ("lam", "wkind", "wmean", "wvar", "fkind", "fmean", "fvar")
    return {k: jnp.stack([jnp.asarray(s[k]) for s in stations], axis=-1)
            for k in keys}


# ---------------------------------------------------------------------------
# exponential-tail asymptote — the cheap method the cluster scan vectorises
# ---------------------------------------------------------------------------


def _mgf_vec(kind, mean, var, eta):
    """Real M_S(eta); garbage (huge finite) past the divergence point, masked
    by the caller. eta broadcasts against the station fields."""
    det = jnp.exp(jnp.minimum(eta * mean, 700.0))
    exp_ = 1.0 / jnp.maximum(1.0 - eta * mean, _TINY)
    gamma_real = var > GAMMA_DET_CV2 * mean * mean
    safe_mean = jnp.where(mean > 0, mean, 1.0)
    safe_var = jnp.where(gamma_real, var, 1.0)
    shape = safe_mean * safe_mean / safe_var
    scale = safe_var / safe_mean
    gam = jnp.exp(jnp.minimum(-shape * jnp.log(jnp.maximum(1.0 - eta * scale, _TINY)),
                              700.0))
    gam = jnp.where(gamma_real, gam, det)
    out = jnp.where(kind == KIND_DET, det, jnp.where(kind == KIND_EXP, exp_, gam))
    return jnp.where(mean > 0, out, jnp.ones_like(out))


def _mgf_prime_vec(kind, mean, var, eta):
    """M_S'(eta) = E[S e^{eta S}], same conventions as ``_mgf_vec``."""
    det = mean * jnp.exp(jnp.minimum(eta * mean, 700.0))
    exp_ = mean / jnp.maximum(1.0 - eta * mean, _TINY) ** 2
    gamma_real = var > GAMMA_DET_CV2 * mean * mean
    safe_mean = jnp.where(mean > 0, mean, 1.0)
    safe_var = jnp.where(gamma_real, var, 1.0)
    shape = safe_mean * safe_mean / safe_var
    scale = safe_var / safe_mean
    gam = mean * jnp.exp(jnp.minimum(
        -(shape + 1.0) * jnp.log(jnp.maximum(1.0 - eta * scale, _TINY)), 700.0))
    gam = jnp.where(gamma_real, gam, det)
    out = jnp.where(kind == KIND_DET, det, jnp.where(kind == KIND_EXP, exp_, gam))
    return jnp.where(mean > 0, out, jnp.zeros_like(out))


def _wait_pole_vec(st):
    """Per-station Cramer decay rate (inf where the station never queues) —
    the vector twin of ``tail._wait_pole``: exp closed form, otherwise
    geometric growth + fixed-iteration bisection with identical constants."""
    lam, wkind = st["lam"], st["wkind"]
    wmean, wvar = st["wmean"], st["wvar"]
    rho = lam * wmean
    safe_wmean = jnp.where(wmean > 0, wmean, 1.0)
    exp_root = (1.0 - rho) / safe_wmean

    def g(eta):
        return lam * (_mgf_vec(wkind, wmean, wvar, eta) - 1.0) - eta

    # divergence point of the wait-service MGF (det -> inf, capped at 700/m)
    gamma_real = wvar > GAMMA_DET_CV2 * wmean * wmean
    safe_var = jnp.where(gamma_real, wvar, 1.0)
    div = jnp.where(
        wkind == KIND_EXP, 1.0 / safe_wmean,
        jnp.where((wkind == KIND_GAMMA) & gamma_real, wmean / safe_var, _INF))
    cap = jnp.minimum(div * (1.0 - 1e-12), 700.0 / safe_wmean)
    hi0 = jnp.minimum(exp_root, cap)

    def grow(_, hi):
        return jnp.where(g(hi) <= 0.0, jnp.minimum(hi * 2.0, cap), hi)

    hi = jax.lax.fori_loop(0, ETA_GROW_ITERS, grow, hi0)

    def bisect(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        le = g(mid) <= 0.0
        return jnp.where(le, mid, lo), jnp.where(le, hi, mid)

    lo, hi = jax.lax.fori_loop(0, ETA_BISECT_ITERS, bisect,
                               (jnp.zeros_like(hi), hi))
    root = jnp.where(wkind == KIND_EXP, exp_root, 0.5 * (lo + hi))
    return jnp.where((lam > 0) & (rho > 0), root, _INF)


def _quantile_asymptote_vec(st, q):
    lam, wmean = st["lam"], st["wmean"]
    rho = lam * wmean
    eta_w = _wait_pole_vec(st)  # (..., S)
    safe_fmean = jnp.where(st["fmean"] > 0, st["fmean"], 1.0)
    eta_s = jnp.where((st["fkind"] == KIND_EXP) & (st["fmean"] > 0),
                      1.0 / safe_fmean, _INF)
    cands = jnp.concatenate([eta_w, eta_s], axis=-1)  # wait poles first
    idx = jnp.argmin(cands, axis=-1)
    eta = jnp.min(cands, axis=-1)
    no_pole = ~jnp.isfinite(eta)
    eta_b = jnp.where(no_pole, 1.0, eta)[..., None]

    # per-station factors at the global eta (garbage at the dominant pole's
    # own factor — excluded from the products below by construction)
    m_w = _mgf_vec(st["wkind"], wmean, st["wvar"], eta_b)
    m_f = _mgf_vec(st["fkind"], st["fmean"], st["fvar"], eta_b)
    g = lam * (m_w - 1.0) - eta_b
    w_fac = jnp.where(rho > 0, (1.0 - rho) * (-eta_b) / jnp.where(
        jnp.abs(g) > _TINY, g, -_TINY), 1.0)
    t_fac = jnp.abs(w_fac) * m_f
    log_t = jnp.log(jnp.maximum(t_fac, _TINY))
    prod_others = jnp.exp(jnp.sum(log_t, axis=-1, keepdims=True) - log_t)

    mgf_p = _mgf_prime_vec(st["wkind"], wmean, st["wvar"], eta_b)
    res_wait = (1.0 - rho) * eta_b / (lam * mgf_p - 1.0) * m_f * prod_others
    res_serv = (1.0 / safe_fmean) * jnp.abs(w_fac) * prod_others
    r_cands = jnp.concatenate([res_wait, res_serv], axis=-1)
    r = jnp.take_along_axis(r_cands, idx[..., None], axis=-1)[..., 0]

    t_q = jnp.log(jnp.maximum(r, _TINY) / (eta_b[..., 0] * (1.0 - q))) / eta_b[..., 0]
    t_q = jnp.where((r > 0) & jnp.isfinite(r), jnp.maximum(t_q, 0.0), _INF)
    return jnp.where(no_pole, jnp.sum(st["fmean"], axis=-1), t_q)


def sojourn_quantile_vec(st: dict, q, *, method: str = "euler",
                         slot_kinds: tuple | None = None,
                         grow_iters: int | None = None):
    """q-quantile of the composed sojourn for station-field arrays (station
    axis last). Traceable; used inside the jitted fleet/cluster paths.

    ``slot_kinds`` is an optional static tuple of per-slot service-kind hints
    for the euler path (``"exp"``/``"nic"`` = statically exponential,
    ``None`` = runtime dispatch) — see
    :func:`repro.fleet.euler_vec.quantile_euler_vec`. ``grow_iters`` is the
    euler path's static bracket-doubling count (``euler_grow_iters(q)``),
    required when q is a tracer. The asymptote path ignores both."""
    unstable = jnp.any(st["lam"] * st["wmean"] >= 1.0, axis=-1)
    if method == "asymptote":
        val = _quantile_asymptote_vec(st, q)
    elif method == "euler":
        val = quantile_euler_vec(st, q, slot_kinds, grow_iters)
    else:
        raise ValueError(f"unknown method {method!r} (known: euler, asymptote)")
    # exact closed form for a pure single M/M/1 station (both methods), as in
    # the scalar layer: t_q = -ln(1-q)/(mu - lam)
    if st["lam"].shape[-1] == 1:
        lam = st["lam"][..., 0]
        mean = st["fmean"][..., 0]
        is_mm1 = ((st["wkind"][..., 0] == KIND_EXP) & (st["fkind"][..., 0] == KIND_EXP)
                  & (st["wmean"][..., 0] == mean) & (mean > 0))
        safe_mean = jnp.where(mean > 0, mean, 1.0)
        exact = -jnp.log1p(-q) / (1.0 / safe_mean - lam)
        val = jnp.where(is_mm1, exact, val)
    return jnp.where(unstable, _INF, val)


# ---------------------------------------------------------------------------
# ScenarioBatch-column station builders (shared with repro.fleet.cluster)
# ---------------------------------------------------------------------------


def _device_stations(c) -> dict:
    """(B, 1) station fields for the on-device path — Eq. 2's single queue."""
    return _stack_stations({
        "lam": c["lam"],
        "wkind": c["dev_model"].astype(jnp.int8),
        "wmean": c["dev_s"] / c["dev_k"],
        "wvar": c["dev_var"],
        "fkind": c["dev_model"].astype(jnp.int8),
        "fmean": c["dev_s"],
        "fvar": c["dev_var"],
    })


def _edge_stations(c) -> dict:
    """(B, E, 3) station fields for the offload path: device NIC -> edge proc
    (own model, or the §3.4 gamma-matched mixture when background tenants are
    present) -> return NIC. Mirrors ``analytic_vec._edge_latency_vec`` so the
    tail and mean evaluations can never drift on inputs."""
    lam = c["lam"][:, None]
    has_bg = c["bg_lam"] > 0.0

    own_var = _implied_var_vec(c["edge_model"], c["edge_s"], c["edge_var"])
    lam_tot = lam + c["bg_lam"]
    mean_mix = (lam * c["edge_s"] + c["bg_wsum"]) / lam_tot
    second_mix = (lam * (own_var + c["edge_s"] ** 2) + c["bg_ssum"]) / lam_tot
    var_mix = jnp.maximum(0.0, second_mix - mean_mix**2)

    b = jnp.where(jnp.isnan(c["edge_bw"]), c["bandwidth_Bps"][:, None], c["edge_bw"])
    req = c["req_bytes"][:, None]
    res = c["res_bytes"][:, None]
    lam_edge = jnp.where(has_bg, lam_tot, lam * jnp.ones_like(lam_tot))
    ret = c["return_results"][:, None]
    res_mean = jnp.where(ret, res / b, 0.0)

    kexp = jnp.full_like(c["edge_model"], KIND_EXP)
    zero = jnp.zeros_like(c["edge_s"])
    proc_kind = jnp.where(has_bg, KIND_GAMMA, c["edge_model"]).astype(jnp.int8)
    nic_in = {"lam": lam * jnp.ones_like(c["edge_s"]), "wkind": kexp,
              "wmean": req / b, "wvar": zero, "fkind": kexp, "fmean": req / b,
              "fvar": zero}
    proc = {"lam": lam_edge, "wkind": proc_kind,
            "wmean": jnp.where(has_bg, mean_mix, c["edge_s"]) / c["edge_k"],
            "wvar": jnp.where(has_bg, var_mix, c["edge_var"]),
            "fkind": proc_kind,
            "fmean": jnp.where(has_bg, mean_mix, c["edge_s"]),
            "fvar": jnp.where(has_bg, var_mix, c["edge_var"])}
    nic_out = {"lam": lam_edge, "wkind": kexp, "wmean": res_mean, "wvar": zero,
               "fkind": kexp, "fmean": res_mean, "fvar": zero}
    return _stack_stations(nic_in, proc, nic_out)


def _device_tail_vec(c, q, method: str, grow_iters: int | None = None,
                     dev_hint: str | None = None):
    """(B,) on-device q-quantile — the tail twin of ``_device_latency_vec``."""
    return sojourn_quantile_vec(_device_stations(c), q, method=method,
                                slot_kinds=(dev_hint,), grow_iters=grow_iters)


def _edge_tail_vec(c, q, method: str, grow_iters: int | None = None,
                   proc_hint: str | None = None):
    """(B, E) offload q-quantile — the tail twin of ``_edge_latency_vec``.

    The NIC slots of the offload tandem are exponential with ``wmean ==
    fmean`` by construction (``nic_station``), so the euler kernel gets
    static ``"nic"`` hints for slots 0 and 2 — the processing slot gets the
    batch-derived ``proc_hint`` (uniform model column) or runtime dispatch."""
    val = sojourn_quantile_vec(_edge_stations(c), q, method=method,
                               slot_kinds=("nic", proc_hint, "nic"),
                               grow_iters=grow_iters)
    return jnp.where(c["edge_mask"], val, _INF)


@partial(jax.jit, static_argnames=("method", "grow_iters", "dev_hint",
                                   "proc_hint"))
def _fleet_tail_jit(c, q, *, method: str, grow_iters: int | None,
                    dev_hint: str | None, proc_hint: str | None):
    t_dev = _device_tail_vec(c, q, method, grow_iters, dev_hint)
    t_edge = _edge_tail_vec(c, q, method, grow_iters, proc_hint)
    stacked = jnp.concatenate([t_dev[:, None], t_edge], axis=1)
    best = jnp.argmin(stacked, axis=1) - 1
    return t_dev, t_edge, best


def _uniform_kind_hint(kinds: np.ndarray) -> str | None:
    """Static service-kind hint for a concrete model column: ``"det"`` /
    ``"exp"`` when every row dispatches to the same branch (the common case —
    sweeps vary load, not service model), else None (runtime dispatch). The
    hints select formulas, never change them, so this is a pure perf
    derivation — on a uniformly non-gamma batch the euler kernel traces no
    ``log`` at all."""
    k = np.asarray(kinds)
    if k.size and np.all(k == KIND_DET):
        return "det"
    if k.size and np.all(k == KIND_EXP):
        return "exp"
    return None


@dataclass(frozen=True)
class FleetTailPrediction:
    """Per-scenario closed-form q-quantile latencies of one fleet evaluation.

    Mirrors :class:`FleetPrediction` (same ``best_edge`` convention, same
    ``totals`` labelling), but every number is the q-th sojourn quantile
    instead of the mean — the batch form of ``Scenario.analytic_tail``.
    """

    q: float
    t_dev: np.ndarray  # (B,)
    t_edge: np.ndarray  # (B, E)
    best_edge: np.ndarray  # (B,) int

    @property
    def size(self) -> int:
        return int(self.t_dev.shape[0])

    def strategy_names(self) -> list[str]:
        return ["on_device" if j < 0 else f"edge[{j}]"
                for j in self.best_edge.tolist()]

    def totals(self, i: int) -> dict[str, float]:
        out = {"on_device": float(self.t_dev[i])}
        for j in range(self.t_edge.shape[1]):
            out[f"edge[{j}]"] = float(self.t_edge[i, j])
        return out


def fleet_tail(batch: ScenarioBatch, q: float, *, method: str = "euler") -> FleetTailPrediction:
    """q-quantile end-to-end latency of every scenario/strategy, one jitted
    call — matches ``Scenario.analytic_tail(q, method=...)`` per row to
    <= 1e-6 relative (gated by the validation harness)."""
    if not 0.0 < q < 1.0:
        raise ValueError(f"quantile must be in (0, 1), got {q}")
    if method not in ("euler", "asymptote"):
        raise ValueError(f"unknown method {method!r} (known: euler, asymptote)")
    method = resolve_tail_method(q, method)
    grow_iters = euler_grow_iters(q) if method == "euler" else None
    np_arrays = batch.arrays()
    dev_hint = _uniform_kind_hint(np_arrays["dev_model"])
    proc_hint = None
    if not np.any(np.asarray(np_arrays["bg_lam"]) > 0.0):
        proc_hint = _uniform_kind_hint(np_arrays["edge_model"])
    with x64():
        arrays = {k: jnp.asarray(v) for k, v in np_arrays.items()}
        t_dev, t_edge, best = _fleet_tail_jit(arrays, jnp.float64(q),
                                              method=method,
                                              grow_iters=grow_iters,
                                              dev_hint=dev_hint,
                                              proc_hint=proc_hint)
        return FleetTailPrediction(
            q=q,
            t_dev=np.asarray(t_dev),
            t_edge=np.asarray(t_edge),
            best_edge=np.asarray(best),
        )
