"""Serving launcher CLI: engine + Poisson workload + Algorithm-1 gateway.

Serves a model through the slot-based engine while the offload gateway
replays a bandwidth schedule and reports its decisions — the deployable shape
of the paper's resource manager. The model is the reduced CPU-runnable proxy
of the architecture unless ``--full-config`` asks for its published widths.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch starcoder2_3b \
      --requests 8 --rps 20 --schedule 20,10,2,20
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.base import ARCH_IDS, get_config
from repro.core.latency import ServiceModel, Tier, Workload
from repro.jaxenv import enable_compilation_cache
from repro.models import lm
from repro.models.params import tree_bytes
from repro.obs import AuditLog, MetricsRegistry, format_decision
from repro.serving.engine import Engine, ServeConfig
from repro.serving.gateway import EdgeHandle, OffloadGateway
from repro.serving.workload import PoissonWorkload, WorkloadConfig

__all__ = ["main"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", choices=ARCH_IDS, default="starcoder2_3b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rps", type=float, default=20.0)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--schedule", type=str, default="20,10,2,20",
                    help="bandwidth schedule in Mbps, one epoch each")
    ap.add_argument("--full-config", action="store_true",
                    help="serve the published-width config (default: reduced CPU proxy)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = cfg.reduced(seq_chunk=8)
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    print(f"[serve] {cfg.name}: d_model={cfg.d_model} "
          f"layers={cfg.num_superblocks * len(cfg.superblock)} dtype={cfg.dtype} "
          f"params={tree_bytes(params)} bytes on {jax.devices()[0].device_kind}")
    engine = Engine(cfg, params, ServeConfig(slots=args.slots, max_seq=64))

    # warmup first so JIT compilation never pollutes the profiled service
    engine.warmup([args.prompt_len])
    wl_gen = PoissonWorkload(WorkloadConfig(
        arrival_rate=args.rps, prompt_len=args.prompt_len,
        max_new_tokens=args.max_new, vocab=cfg.vocab_size,
    ))
    for r in wl_gen.take(args.requests):
        engine.submit(r)
    engine.drain()
    s_dev, var = engine.observed_service_stats()
    lat = [r.latency_s for r in engine.completed if r.latency_s is not None]
    print(f"[serve] {len(engine.completed)} requests done; "
          f"profiled tick {s_dev*1e3:.1f} ms (var {var:.2e})")

    dev = Tier("device-engine", s_dev, service_model=ServiceModel.EXPONENTIAL)
    # payloads scaled to the profiled service: the schedule's bandwidth
    # crossover lands near 5 Mbps regardless of machine speed
    req_bytes = max(1, int(0.8 * s_dev * 0.625e6))
    # every per-epoch line below is rendered FROM the audit log, so the
    # console report and the machine-readable trail cannot disagree
    auditor = AuditLog()
    metrics = MetricsRegistry()
    gw = OffloadGateway(
        dev,
        [EdgeHandle("edge0", service_mean_s=s_dev / 8, parallelism_k=4.0)],
        Workload(args.rps, req_bytes, max(1, req_bytes // 5)),
        bandwidth_Bps=2.5e6,
        auditor=auditor,
        metrics=metrics,
    )
    for i, mbps in enumerate(float(x) for x in args.schedule.split(",")):
        for _ in range(3):
            gw.observe_bandwidth(mbps * 1e6 / 8)
        for dt in np.arange(0.0, 1.0, 1.0 / max(args.rps, 1.0)):
            gw.observe_arrival(i + dt)
        gw.decide(now=i + 1.0)
        print(format_decision(auditor.rows[-1]))
    auditor.verify()  # terms must re-sum to the decision totals
    print(f"[gateway] switches={gw.switches}")
    for line in metrics.render().splitlines():
        print(f"[metrics] {line}")
    # every submitted request must come back answered
    return 0 if len(engine.completed) == args.requests else 1


if __name__ == "__main__":
    enable_compilation_cache()
    raise SystemExit(main())
