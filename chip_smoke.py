"""Smoke run of the served path and the decision path on one TPU chip.

Runs in one process and checks every phase against its own oracle:

* served: ``repro.launch.serve`` answers a few requests with starcoder2_3b at
  published widths (bf16), ``repro.launch.measure profile --clock wall``
  profiles 16 requests at one slot, and the engine's first token for a fixed
  prompt equals the argmax of ``lm.forward`` on the same prompt;
* decision: ``fleet_analytic`` over a 1024 x 1024 sweep against scalar
  ``analytic()`` rows, ``fleet_tail(method="euler")`` over the golden corpus
  against scalar ``analytic_tail``, ``simulate_cluster`` on the 64-client
  default fleet (adaptive must win), and the mean-field equilibrium of a
  million clients (must converge).

``--four-chips`` runs only the sharded exact cluster solver
(``simulate_cluster(..., shards=4)`` through ``jax.shard_map``) against
``shards=1`` on the same spec and trace.

Usage, from the repository root on a machine with a TPU:
  python3 chip_smoke.py
  python3 chip_smoke.py --four-chips

The last line of standard output is one JSON object naming the device; any
failed check exits nonzero before it is printed. There is no CPU fallback.
"""

from __future__ import annotations

import argparse
import collections
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
RESULTS = ROOT / "results" / "chip_smoke"

ARCH = "starcoder2_3b"
ANALYTIC_TOL = 1e-9  # fleet_analytic rows vs scalar analytic(), relative
EULER_TOL = 1e-8  # fleet_tail(euler) vs scalar analytic_tail, relative
SHARD_TOL = 1e-12  # shards=4 vs shards=1 latencies: reassociation only


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _rel_err(a: float, b: float) -> float:
    """Symmetric relative error; same-sign infinities agree, NaN never does."""
    if a != a or b != b:
        return float("inf")
    if a == b:
        return 0.0
    return abs(a - b) / max(abs(a), abs(b))


class CompileClock:
    """Sums JAX's backend-compile durations and cache hits per phase."""

    def __init__(self):
        import jax

        self.totals = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.totals["compile_s"] += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.totals["cache_hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.totals["cache_misses"] += 1

    def snapshot(self) -> dict:
        return dict(self.totals)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def served_phase(*, full_config: bool = True, serve_requests: int = 4,
                 profile_requests: int = 16) -> dict:
    """Serve and profile ``ARCH`` through the launchers, then check the
    engine's first token against the full forward pass."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.launch import measure, serve
    from repro.measure import load_profile
    from repro.models import lm
    from repro.models.params import tree_bytes
    from repro.serving.engine import Engine, Request, ServeConfig

    width = ["--full-config"] if full_config else []
    rc = serve.main(["--arch", ARCH, "--requests", str(serve_requests),
                     "--slots", "1", *width])
    _check(rc == 0, f"repro.launch.serve exited {rc}")
    gc.collect()

    profile_path = RESULTS / f"PROFILE_{ARCH}.json"
    rc = measure.main(["profile", "--config", ARCH, "--clock", "wall",
                       "--slots", "1", "--requests", str(profile_requests),
                       "--out", str(profile_path), *width])
    _check(rc == 0, f"repro.launch.measure profile exited {rc}")
    profile = load_profile(profile_path)
    _check(profile.n_requests == profile_requests,
           f"profile recorded {profile.n_requests} of {profile_requests} requests")
    gc.collect()

    cfg = get_config(ARCH)
    if not full_config:
        cfg = cfg.reduced(seq_chunk=8)
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, 12).astype(np.int32)
    eng = Engine(cfg, params, ServeConfig(slots=1, max_seq=64))
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=2))
    eng.drain()
    _check(len(eng.completed) == 1, "engine did not answer the fixed prompt")
    first = eng.completed[0].tokens_out[0]
    logits = jax.jit(lambda p, t: lm.forward(p, cfg, t))(params, jnp.asarray(prompt[None]))
    last = np.asarray(logits[0, -1], np.float32)
    ref = int(np.argmax(last))
    top2 = np.sort(last)[-2:]
    _check(first == ref, f"engine first token {first} != argmax of lm.forward {ref} "
                         f"(top-2 logit gap {top2[1] - top2[0]:.4g})")
    stats = jax.devices()[0].memory_stats() or {}
    return {
        "arch": cfg.name,
        "d_model": cfg.d_model,
        "layers": cfg.num_superblocks * len(cfg.superblock),
        "dtype": cfg.dtype,
        "param_bytes": tree_bytes(params),
        "serve_requests": serve_requests,
        "profile_requests": profile.n_requests,
        "first_token": first,
        "top2_logit_gap": float(top2[1] - top2[0]),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }


def decision_phase(*, grid: int = 1024, samples: int = 16, clients: int = 64,
                   mf_clients: int = 1_000_000) -> dict:
    """The jitted decision path, each piece against its own oracle."""
    import numpy as np

    from repro.core.scenario import analytic_tail
    from repro.fleet import (
        ScenarioBatch,
        fleet_analytic,
        fleet_tail,
        simulate_cluster,
        solve_meanfield_equilibrium,
    )
    from repro.launch.cluster_sim import default_cluster, default_meanfield
    from repro.launch.fleet_sweep import default_scenario
    from repro.validate.corpus import load_corpus
    from repro.validate.differential import EULER_VEC_RHO_MAX

    out = {}
    # -- fleet_analytic over a grid x grid sweep ------------------------------
    base = default_scenario()
    lam = np.geomspace(0.1, 20.0, grid)
    bw = np.geomspace(1e4, 1e8, grid)
    axes = {"workload.arrival_rate": lam, "network.bandwidth_Bps": bw}
    pred = fleet_analytic(ScenarioBatch.from_sweep(base, axes))
    _check(pred.size == grid * grid, f"fleet_analytic returned {pred.size} rows")
    rows = np.random.default_rng(0).choice(grid * grid, size=samples, replace=False)
    worst = 0.0
    for r in rows.tolist():
        i, j = divmod(r, grid)
        scn = base.replaced("workload.arrival_rate", float(lam[i])) \
            .replaced("network.bandwidth_Bps", float(bw[j]))
        vec = pred.totals(r)
        worst = max(worst, max(_rel_err(v, vec[k])
                               for k, v in scn.analytic().totals().items()))
    _check(worst <= ANALYTIC_TOL, f"fleet_analytic vs analytic(): {worst:.3g}")
    out["analytic_rows"] = pred.size
    out["analytic_max_rel_err"] = worst

    # -- fleet_tail(method="euler") over the golden corpus --------------------
    entries, _ = load_corpus()
    tails = fleet_tail(ScenarioBatch.from_scenarios([e.scenario for e in entries]),
                       0.99, method="euler")
    gated = [i for i, e in enumerate(entries) if e.rho <= EULER_VEC_RHO_MAX]
    worst = 0.0
    for i in gated:
        vec = tails.totals(i)
        worst = max(worst, max(_rel_err(v, vec[k]) for k, v in
                               analytic_tail(entries[i].scenario, 0.99,
                                             method="euler").items()))
    _check(worst <= EULER_TOL, f"fleet_tail(euler) vs analytic_tail: {worst:.3g}")
    out["euler_entries"] = len(gated)
    out["euler_max_rel_err"] = worst

    # -- closed-loop exact cluster --------------------------------------------
    spec = default_cluster(clients)
    res = simulate_cluster(spec, _cluster_trace(spec),
                           policies=("adaptive", "on_device") + tuple(
                               f"edge[{j}]" for j in range(spec.n_edges)),
                           stagger=8)
    _check(res.adaptive_wins, "simulate_cluster: adaptive does not beat every static")
    out["cluster_client_epochs"] = res.client_epochs
    out["cluster_adaptive_mean_s"] = res.policies["adaptive"].mean_latency_s

    # -- million-client mean-field equilibrium --------------------------------
    eq = solve_meanfield_equilibrium(default_meanfield(mf_clients))
    _check(eq.converged, f"mean-field equilibrium did not converge "
                         f"(regret {eq.regret_pct:.3g}%)")
    out["meanfield_clients"] = mf_clients
    out["meanfield_iterations"] = eq.iterations
    return out


def _cluster_trace(spec, duration: float = 180.0):
    """The cluster CLI's default walk: bandwidth drops to 0.15x for the
    middle third."""
    import numpy as np

    from repro.fleet import make_trace, step_signal

    bw0 = float(np.asarray(spec.base.network.bandwidth_Bps))
    third = duration / 3
    return make_trace(
        duration, 1.0,
        bandwidth_Bps=lambda t: step_signal(
            t, [(0.0, bw0), (third, 0.15 * bw0), (2 * third, bw0)]),
        arrival_rate=spec.base.workload.arrival_rate)


def four_chip_phase(*, clients: int = 256, shards: int = 4) -> dict:
    """``simulate_cluster`` sharded over ``shards`` devices vs one."""
    import jax
    import numpy as np

    from repro.fleet import simulate_cluster
    from repro.launch.cluster_sim import default_cluster

    # fleet/cluster.py takes the vmapped single-device branch when fewer
    # devices exist than shards; refuse that here
    _check(len(jax.devices()) == shards,
           f"need {shards} devices for the shard_map path, have {len(jax.devices())}")
    spec = default_cluster(clients)
    trace = _cluster_trace(spec)
    kw = dict(policies=("adaptive",), stagger=8, seed=7)
    one = simulate_cluster(spec, trace, **kw).policies["adaptive"]
    many = simulate_cluster(spec, trace, shards=shards, **kw).policies["adaptive"]
    _check(np.array_equal(one.choices, many.choices),
           "shards=4 decisions differ from shards=1")
    err = float(np.max(np.abs(many.latencies_s - one.latencies_s)
                       / np.maximum(np.abs(one.latencies_s), 1e-300)))
    _check(err <= SHARD_TOL, f"shards=4 latencies differ from shards=1: {err:.3g}")
    return {"clients": clients, "shards": shards,
            "decisions": int(one.choices.size), "max_rel_err": err}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded cluster solver across four chips")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"error: {ROOT} holds no repro checkout (src/repro missing)",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"error: no TPU found (JAX platform {dev.platform!r}); "
              "this smoke run has no CPU fallback", file=sys.stderr)
        return 1

    from repro.jaxenv import enable_compilation_cache

    _log(f"device {dev.device_kind} x {len(jax.devices())}, "
         f"compile cache {enable_compilation_cache()}")
    RESULTS.mkdir(parents=True, exist_ok=True)
    clock = CompileClock()
    phases = [("four_chips", four_chip_phase)] if args.four_chips else \
        [("served", served_phase), ("decision", decision_phase)]
    for name, phase in phases:
        before, t0 = clock.snapshot(), time.perf_counter()
        info = phase()
        after = clock.snapshot()
        info["wall_s"] = time.perf_counter() - t0
        for key in ("compile_s", "cache_hits", "cache_misses"):
            info[key] = after.get(key, 0) - before.get(key, 0)
        _log(f"{name} phase passed: {json.dumps(info, sort_keys=True)}")

    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
