"""Batched discrete-event simulation as a `jax.lax.scan` Lindley recursion.

`repro.core.simulation` simulates one scenario at a time with a Python-loop
Lindley recursion (exact, but ~1e5 interpreter steps per scenario). Here the
same feed-forward tandem FCFS networks run for *thousands of scenarios in one
device launch*: the job axis is a `lax.scan`, the scenario axis is pure
vectorization, and k-server stations keep a (B, k) earliest-free-server state
updated with a masked argmin — the scan translation of the heap in
``simulation.station_pass`` (identical departures; only tie-breaking among
equal-free servers can differ, which cannot change any departure time).

Semantics mirror ``scenario.simulate`` exactly for dedicated-edge and
on-device strategies: Poisson arrivals, per-tier service distributions derived
from the ServiceModel (deterministic / exponential / lognormal-general),
exponential NIC stages with mean D/B, and inter-stage resorting by departure
where k > 1 allows overtaking. Multi-tenant edges need the shared-station
merge and are delegated to the scalar simulator (raised here, not silently
mis-simulated).

A Pallas kernel variant of the k=1 recursion lives in
``repro.kernels.lindley_scan`` (same contract as :func:`lindley_station` with
``k=1``); the scan path is the portable default.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.simulation import steady_slice
from repro.jaxenv import x64

from .batch import ScenarioBatch

__all__ = ["FleetSimResult", "lindley_station", "simulate_fleet"]


@partial(jax.jit, static_argnames=("k_max",))
def _lindley_station_jit(arrivals, services, k, *, k_max: int):
    b, _n = arrivals.shape
    # per-scenario server pool: slots >= k_i start (and stay) at +inf so the
    # masked argmin never selects them — a padded server is never free first
    slot = jnp.arange(k_max)
    free0 = jnp.where(slot[None, :] < k[:, None], 0.0, jnp.inf)

    def step(free, job):
        arr, svc = job
        idx = jnp.argmin(free, axis=1)
        earliest = jnp.take_along_axis(free, idx[:, None], axis=1)[:, 0]
        start = jnp.maximum(arr, earliest)
        dep = start + svc
        free = free.at[jnp.arange(b), idx].set(dep)
        return free, dep

    _, deps = jax.lax.scan(step, free0, (arrivals.T, services.T))
    return deps.T


def lindley_station(arrivals, services, k=1, *, k_max: int | None = None):
    """FCFS k-server station, batched: departure times for (B, N) arrivals.

    The exact scan counterpart of ``simulation.station_pass`` — jobs start in
    arrival order on the earliest-free server. ``k`` may be an int (shared) or
    a (B,) array of per-scenario server counts; ``k_max`` bounds the packed
    server state (defaults to max(k)).
    """
    k_needed = int(np.max(np.asarray(k)))
    if k_max is None:
        k_max = k_needed
    elif k_max < k_needed:
        raise ValueError(
            f"k_max={k_max} is smaller than the largest server count "
            f"{k_needed}; the station would silently run with fewer servers")
    # float64 throughout: arrival clocks reach ~n/lam, and float32 ulps there
    # would swamp millisecond-scale waits
    with x64():
        arrivals = jnp.asarray(np.asarray(arrivals, dtype=np.float64))
        services = jnp.asarray(np.asarray(services, dtype=np.float64))
        k_arr = jnp.broadcast_to(jnp.asarray(k, dtype=jnp.int32), arrivals.shape[:1])
        return _lindley_station_jit(arrivals, services, k_arr, k_max=k_max)


def _resort_by_departure(dep, orig_arrival):
    """FCFS order at the next station is by arrival there (= departure here);
    carry each job's original arrival through the permutation."""
    perm = jnp.argsort(dep, axis=1, stable=True)
    return jnp.take_along_axis(dep, perm, axis=1), jnp.take_along_axis(
        orig_arrival, perm, axis=1
    )


def _service_samples(key, model, s, var, shape):
    """(B, N) service draws per scenario row, dispatching on MODEL_CODES:
    deterministic / exponential / lognormal(mean, var) — the same three
    distributions ``scenario._service_dist`` derives."""
    kn, kl = jax.random.split(key)
    s = s[:, None]
    var = var[:, None]
    exp_draw = s * jax.random.exponential(kn, shape)
    # LogNormal(mean, var) moment-matched exactly as simulation.LogNormal
    sigma2 = jnp.log1p(var / (s * s))
    mu = jnp.log(s) - 0.5 * sigma2
    ln_draw = jnp.exp(mu + jnp.sqrt(sigma2) * jax.random.normal(kl, shape))
    ln_draw = jnp.where(var == 0.0, s, ln_draw)  # degenerate general -> constant
    model = model[:, None]
    return jnp.where(model == 0, s, jnp.where(model == 1, exp_draw, ln_draw))


@dataclass(frozen=True)
class FleetSimResult:
    """Observed per-scenario latencies of one batched simulation."""

    latencies: np.ndarray  # (B, N) in original arrival order
    arrivals: np.ndarray  # (B, N)
    warmup_frac: float = 0.1

    def _steady(self) -> np.ndarray:
        return self.latencies[:, steady_slice(self.latencies.shape[1],
                                              self.warmup_frac)]

    @property
    def mean(self) -> np.ndarray:
        """(B,) steady-state mean latency per scenario."""
        return self._steady().mean(axis=1)

    def percentile(self, q: float) -> np.ndarray:
        return np.percentile(self._steady(), q, axis=1)


def simulate_fleet(
    batch: ScenarioBatch,
    strategy: str = "on_device",
    *,
    n: int = 20_000,
    seed: int = 0,
    k_max: int | None = None,
) -> FleetSimResult:
    """Simulate every scenario in the batch under one strategy, one launch.

    ``strategy`` is ``"on_device"`` or ``"edge[j]"`` (dedicated edges only —
    rows whose target edge hosts background tenants raise, because the shared
    multi-tenant station needs the scalar ``scenario.simulate`` path). The
    trim/mean conventions match ``simulation.SimResult`` so per-scenario means
    are directly comparable against ``simulate_tandem`` on the same spec.
    """
    m = re.fullmatch(r"on_device|edge\[(\d+)\]", strategy)
    if not m:
        raise ValueError(f"unknown strategy {strategy!r}")
    edge = None if m.group(1) is None else int(m.group(1))

    with x64():
        key = jax.random.PRNGKey(seed)
        keys = jax.random.split(key, 4)
        shape = (batch.size, n)

        inter = jax.random.exponential(keys[0], shape) / jnp.asarray(batch.lam)[:, None]
        arrivals = jnp.cumsum(inter, axis=1)

        if edge is None:
            k_dev = np.rint(batch.dev_k).astype(np.int64)
            if not np.all(k_dev == batch.dev_k):
                raise ValueError("fractional device parallelism_k cannot be simulated "
                                 "exactly; round it or compare via fleet_analytic only")
            services = _service_samples(
                keys[1], jnp.asarray(batch.dev_model), jnp.asarray(batch.dev_s),
                jnp.asarray(batch.dev_var), shape,
            )
            dep = lindley_station(arrivals, services, np.maximum(k_dev, 1), k_max=k_max)
            latencies = dep - arrivals
            return FleetSimResult(np.asarray(latencies), np.asarray(arrivals))

        if edge >= batch.max_edges or not bool(np.all(batch.edge_mask[:, edge])):
            raise ValueError(f"strategy {strategy!r}: not every scenario has that edge")
        if np.any(batch.bg_lam[:, edge] > 0):
            raise ValueError(
                f"strategy {strategy!r}: background tenants need the shared-station "
                "simulator — use scenario.simulate for those rows"
            )
        k_edge = np.rint(batch.edge_k[:, edge]).astype(np.int64)
        if not np.all(k_edge == batch.edge_k[:, edge]):
            raise ValueError("fractional edge parallelism_k cannot be simulated "
                             "exactly; round it or compare via fleet_analytic only")

        bw = np.where(np.isnan(batch.edge_bw[:, edge]), batch.bandwidth_Bps,
                      batch.edge_bw[:, edge])
        req_mean = jnp.asarray(batch.req_bytes / bw)[:, None]
        res_mean = jnp.asarray(
            np.where(batch.return_results, batch.res_bytes, 0.0) / bw
        )[:, None]

        # stage 1: device NIC (k=1, exponential mean D_req/B); k=1 departures
        # are already non-decreasing, so no resort is needed before stage 2
        nic_req = req_mean * jax.random.exponential(keys[1], shape)
        t = lindley_station(arrivals, nic_req, 1, k_max=1)
        orig = arrivals

        # stage 2: edge processing (k servers, tier service model)
        services = _service_samples(
            keys[2], jnp.asarray(batch.edge_model[:, edge]),
            jnp.asarray(batch.edge_s[:, edge]), jnp.asarray(batch.edge_var[:, edge]),
            shape,
        )
        dep = lindley_station(t, services, np.maximum(k_edge, 1), k_max=k_max)
        t, orig = _resort_by_departure(dep, orig)  # k>1 can overtake

        # stage 3: edge NIC return path (k=1, exponential mean D_res/B; zero
        # mean collapses to zero service when results are consumed at the edge)
        nic_res = res_mean * jax.random.exponential(keys[3], shape)
        dep = lindley_station(t, nic_res, 1, k_max=1)

        latency = dep - orig
        # report in original arrival order for warmup trimming (cf. SimResult)
        perm = jnp.argsort(orig, axis=1, stable=True)
        latency = jnp.take_along_axis(latency, perm, axis=1)
        orig = jnp.take_along_axis(orig, perm, axis=1)
        return FleetSimResult(np.asarray(latency), np.asarray(orig))
