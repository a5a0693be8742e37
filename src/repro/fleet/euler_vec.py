"""Fast batched exact Pollaczek-Khinchine quantile inversion (the tentpole
behind ``fleet_tail(batch, q, method="euler")`` being *real*).

The first vectorized euler path transcribed the scalar algorithm literally:
64 geometric bracket-growth steps plus 100 bisections, each a full Abate-Whitt
contour evaluation, with every service-distribution branch (det / exp / gamma)
computed for every station before a ``where``-select. That is 164 contour
evaluations x 27 complex-LST products x 3 stations per scenario row — ~170x
slower than the exponential-tail asymptote, which is why every batch consumer
traded correctness for speed. This module gets the exact inversion within an
order of magnitude of the asymptote by attacking both factors:

  * **q-derived growth schedule** — Markov's inequality caps the q-quantile
    at ``mean/(1-q)``, so ``euler_grow_iters(q)`` ~ ``log2(1/(1-q)) + 1``
    doublings from ``2 * mean`` always bracket it. The scalar's 64 blind
    doublings become ~8 for p99 (the schedule is shared — see below).
  * **Safeguarded Newton with a free density** — Abate-Whitt inverts any
    transform on the same contour: the CDF uses ``T*(theta)/theta`` and the
    density uses ``T*(theta)`` bare, so one set of transform evaluations
    yields both F(t) and f(t). After ``EULER_BISECT_ITERS`` bisections have
    isolated the crossing, each Newton iteration takes the step when it lands
    inside the current bracket and the bisection midpoint otherwise. The
    scalar's 100 blind bisections become 12 + 10.
  * **One transcendental pair per service evaluation** — det and gamma LSTs
    are both ``exp(·)`` of a selected exponent (``-theta m`` vs
    ``-shape log(1 + theta scale)``), so selecting the *exponent* and
    exponentiating once replaces two complex ``exp`` + one complex ``log``
    with one of each. Slots whose service is *statically* exponential — the
    NIC stations of every offload tandem — skip the transcendentals entirely
    via the ``slot_kinds`` hints (a pure-rational LST), and the ``"nic"``
    hint additionally reuses the one LST for both the wait and the full
    service factor (NIC stations have ``wmean == fmean`` by construction).

Numerical contract — why the trajectory is shared, not just the CDF: the
Euler-inverted CDF of near-deterministic mixtures (M/D/1-heavy tandems)
carries oscillatory inversion noise of amplitude ~``e^-A`` *relative to the
jump structure*, with wavelength ~``t/(N+M+1)``; near a quantile level that
noise can produce several crossings, and two different-but-correct root
finders will land on different ones (observed: 30% apart on a corpus M/D/1
entry). The <= 1e-8 scalar-vs-vec agreement gate therefore requires both
sides to walk the IDENTICAL evaluation sequence. ``quantile_euler_vec``
replays ``core.tail._quantile_euler`` phase for phase — same start
``max(2 * mean, 1e-12)``, same doubling schedule, same bisection midpoints,
same Newton formula and safeguard — on a CDF computed by the same formulas
term for term (``exp(where(c, a, b)) == where(c, exp(a), exp(b))``), so the
two sides agree to float-noise (~1e-11 over the golden corpus), and the
differential harness gates it at <= 1e-8 (``tail-euler-vec`` check). The
three phases share one loop body, so the contour is traced and compiled once.

The contour is carried as paired real/imaginary float64 arrays: complex
``exp``, ``log``, multiply and divide are spelled out in real arithmetic
(``_cexp``, ``_clog``, ``_cmul``, ``_cdiv``), so no complex value reaches the
compiler. TPU compilers refuse complex128, and this keeps the exact inversion
on the chip rather than on the host.

A Pallas kernel variant was considered and skipped: the inner loop is
dominated by transcendentals over a (rows, 27)-point contour, which XLA
already fuses into a handful of elementwise kernels, and the transcendental
mix leaves no tiling structure for a TPU kernel to exploit beyond what the
fused elementwise path gets.

Import direction: this module must not import ``tail_vec`` (which routes its
euler method here) — the shared station-dict helpers it needs live locally.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tail import (
    EULER_A,
    EULER_BISECT_ITERS,
    EULER_M,
    EULER_N,
    EULER_NEWTON_ITERS,
    GAMMA_DET_CV2,
    KIND_EXP,
    KIND_GAMMA,
    _EULER_WEIGHTS,
    euler_grow_iters,
)

__all__ = ["cdf_pdf_vec", "quantile_euler_vec"]

_INF = jnp.inf
_TINY = 1e-300

# contour index k, its Euler sign (k = 0 term halved) and the summation window
_KS = np.arange(EULER_N + EULER_M + 1, dtype=np.float64)
_SIGN = np.where(_KS == 0, 0.5, 1.0) * (-1.0) ** _KS
_WINDOW = slice(EULER_N, EULER_N + EULER_M + 1)
_ONE = (1.0, 0.0)


# complex arithmetic on (re, im) pairs of float64 arrays


def _cmul(a, b):
    return a[0] * b[0] - a[1] * b[1], a[0] * b[1] + a[1] * b[0]


def _cdiv(a, b):
    den = b[0] * b[0] + b[1] * b[1]
    return (a[0] * b[0] + a[1] * b[1]) / den, (a[1] * b[0] - a[0] * b[1]) / den


def _cexp(z):
    mag = jnp.exp(z[0])
    return mag * jnp.cos(z[1]), mag * jnp.sin(z[1])


def _clog(z):
    return 0.5 * jnp.log(z[0] * z[0] + z[1] * z[1]), jnp.arctan2(z[1], z[0])


def _cwhere(c, a, b):
    return jnp.where(c, a[0], b[0]), jnp.where(c, a[1], b[1])


def _slot_service_lst(kind, mean, var, theta, hint):
    """LST E[e^{-theta S}] for one slot's service distribution, as a
    (re, im) pair.

    ``hint`` is the slot's static service-kind hint: ``"exp"`` / ``"nic"``
    mean every row's ``kind`` is KIND_EXP by construction (NIC slots, or a
    batch whose model column is uniformly exponential), so the LST is the
    pure rational ``1/(1 + theta m)`` — no transcendentals traced at all.
    ``"det"`` means uniformly KIND_DET: one complex ``exp``, no log. ``None``
    keeps the runtime dispatch, restructured as exponent-select + a single
    ``exp``: det and degenerate-gamma use ``-theta m``, real gamma uses
    ``-shape log(1 + theta scale)`` (identical values to the scalar branches,
    one complex exp + one complex log instead of two and one). ``mean == 0``
    is the inert factor 1, as everywhere in the tail layer.
    """
    tr, ti = theta
    if hint in ("exp", "nic"):
        out = _cdiv(_ONE, (1.0 + tr * mean, ti * mean))
        return _cwhere(mean > 0, out, _ONE)
    if hint == "det":
        out = _cexp((-tr * mean, -ti * mean))
        return _cwhere(mean > 0, out, _ONE)
    exp_ = _cdiv(_ONE, (1.0 + tr * mean, ti * mean))
    gamma_real = var > GAMMA_DET_CV2 * mean * mean  # tail.GAMMA_DET_CV2 cutoff
    safe_mean = jnp.where(mean > 0, mean, 1.0)
    safe_var = jnp.where(gamma_real, var, 1.0)
    shape = safe_mean * safe_mean / safe_var
    scale = safe_var / safe_mean
    use_gamma = (kind == KIND_GAMMA) & gamma_real
    lg = _clog((1.0 + tr * scale, ti * scale))
    expo = _cwhere(use_gamma, (-shape * lg[0], -shape * lg[1]),
                   (-tr * mean, -ti * mean))
    out = _cwhere(kind == KIND_EXP, exp_, _cexp(expo))
    return _cwhere(mean > 0, out, _ONE)


def _total_lst_slots(st, theta, slot_kinds):
    """Product of per-slot sojourn transforms ``W* Sf*`` at ``theta``
    (trailing contour axis K), as a (re, im) pair. The slot loop is unrolled
    in Python — S is 1 (device) or 3 (offload tandem) — so each slot's
    static hint can prune its traced branches independently. Hint ``"nic"``
    additionally asserts ``wmean == fmean`` (true for every
    ``nic_station``), letting the wait factor reuse the full-service LST
    instead of re-evaluating it.
    """
    tr, ti = theta
    n_slots = st["lam"].shape[-1]
    if slot_kinds is None:
        slot_kinds = (None,) * n_slots
    out = None
    for s in range(n_slots):
        hint = slot_kinds[s]
        lam = st["lam"][..., s, None]
        wmean = st["wmean"][..., s, None]
        rho = lam * wmean
        f = _slot_service_lst(st["fkind"][..., s, None], st["fmean"][..., s, None],
                              st["fvar"][..., s, None], theta, hint)
        if hint == "nic":
            sw = f
        else:
            sw = _slot_service_lst(st["wkind"][..., s, None], wmean,
                                   st["wvar"][..., s, None], theta, hint)
        # W*(theta) = (1 - rho) theta / (theta - lam (1 - Sw*(theta)))
        w = _cdiv(((1.0 - rho) * tr, (1.0 - rho) * ti),
                  (tr - lam * (1.0 - sw[0]), ti + lam * sw[1]))
        fac = _cmul(_cwhere(rho > 0, w, _ONE), f)
        out = fac if out is None else _cmul(out, fac)
    return out


def _implied_var_st(kind, mean, var):
    return jnp.where(kind == KIND_EXP, mean * mean,
                     jnp.where(kind == KIND_GAMMA, var, 0.0))


def _sojourn_mean_vec(st):
    """Per-path mean: sum of P-K waits + full service means (inf past rho=1)."""
    rho = st["lam"] * st["wmean"]
    v = _implied_var_st(st["wkind"], st["wmean"], st["wvar"])
    w = st["lam"] * (st["wmean"] ** 2 + v) / (2.0 * jnp.maximum(1.0 - rho, _TINY))
    w = jnp.where(rho > 0, jnp.where(rho < 1.0, w, _INF), 0.0)
    return jnp.sum(w + st["fmean"], axis=-1)


def _contour(st, t, slot_kinds):
    """theta_k = (A + 2 pi i k) / (2t) and T*(theta_k), both (re, im)."""
    tt = 2.0 * t[..., None]
    theta = (EULER_A / tt, (2.0 * jnp.pi * jnp.asarray(_KS)) / tt)
    return theta, _total_lst_slots(st, theta, slot_kinds)


def _euler_sum(terms, t):
    """Abate-Whitt Euler summation of the real contour terms at ``t``."""
    partial = jnp.cumsum(jnp.asarray(_SIGN) * terms, axis=-1)
    return jnp.exp(EULER_A / 2.0) / t * (partial[..., _WINDOW]
                                         @ jnp.asarray(_EULER_WEIGHTS))


def cdf_pdf_vec(st, t, slot_kinds=None):
    """(CDF, PDF) of the composed sojourn at ``t``, one contour evaluation.

    Abate-Whitt inversion applies to any transform on the same contour
    ``theta_k = (A + 2 pi i k) / (2t)``: the CDF's transform is
    ``T*(theta)/theta``, the density's is ``T*(theta)`` itself. Sharing the
    ``T*`` evaluations is what makes Newton's derivative free. Arithmetic
    follows the scalar ``core.tail._cdf_pdf`` on the same station fields,
    in real (re, im) pairs; the PDF is clipped at 0 (inversion noise can dip
    slightly negative in flat regions — the safeguard treats a zero
    derivative as "fall back to bisection").
    """
    theta, vals = _contour(st, t, slot_kinds)
    cdf = jnp.clip(_euler_sum(_cdiv(vals, theta)[0], t), 0.0, 1.0)
    pdf = jnp.maximum(_euler_sum(vals[0], t), 0.0)
    return cdf, pdf


def quantile_euler_vec(st, q, slot_kinds=None, grow_iters=None):
    """q-quantile of the composed sojourn by exact Euler inversion, batched.

    Replays the scalar ``core.tail._quantile_euler`` trajectory phase for
    phase — ``grow_iters`` doublings from ``max(2 * mean, 1e-12)``,
    ``EULER_BISECT_ITERS`` bisections, ``EULER_NEWTON_ITERS`` safeguarded
    Newton steps on the free Abate-Whitt density — so both sides land on the
    same crossing of the same noisy CDF (see module docstring) and agree to
    float-noise, well under the 1e-8 gated tolerance. Unstable rows (infinite
    mean) return inf, matching the scalar layer.

    Traceable; ``slot_kinds`` must be a static tuple of per-slot hints (or
    None) and ``grow_iters`` a static int at trace time. ``grow_iters`` is
    derived from q via ``core.tail.euler_grow_iters`` when q is concrete;
    inside a jit where q is traced it must be passed explicitly.
    """
    if grow_iters is None:
        grow_iters = euler_grow_iters(float(q))  # raises if q is a tracer
    mean = _sojourn_mean_vec(st)
    finite = jnp.isfinite(mean)
    safe_mean = jnp.where(finite, mean, 1.0)
    hi0 = jnp.maximum(2.0 * safe_mean, 1e-12)
    n_search = grow_iters + EULER_BISECT_ITERS

    # One loop over all three phases, so the contour is traced (and
    # compiled) once: iteration i evaluates F and f at the phase's point.
    def step(i, carry):
        lo, hi, t = carry
        grow, search = i < grow_iters, i < n_search
        mid = 0.5 * (lo + hi)
        x = jnp.where(grow, hi, jnp.where(search, mid, t))
        cdf, pdf = cdf_pdf_vec(st, x, slot_kinds)
        below = cdf < q
        # grow: a below-q hi doubles, and becomes the bracket's known lower
        # end (the scalar's free first bisection); bisect and Newton narrow
        lo = jnp.where(below, x, lo)
        hi = jnp.where(below, jnp.where(grow, 2.0 * hi, hi), jnp.where(grow, hi, x))
        newton = x - (cdf - q) / jnp.where(pdf > 0.0, pdf, 1.0)
        ok = (pdf > 0.0) & (newton > lo) & (newton < hi)
        t = jnp.where(search | ~ok, 0.5 * (lo + hi), newton)
        return lo, hi, t

    lo, hi, t = jax.lax.fori_loop(
        0, n_search + EULER_NEWTON_ITERS, step,
        (jnp.zeros_like(hi0), hi0, hi0))
    return jnp.where(finite, jnp.clip(t, lo, hi), _INF)
