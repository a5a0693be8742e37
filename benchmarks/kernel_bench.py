"""Kernel rows for the benchmark CSV + the ``BENCH_kernels.json`` artifact:
reference-path timing + validated max-abs error of the Pallas kernel
(interpret mode) at a representative shape.

``max_abs_err`` values are headline-gated by ``check_regression`` (a 10x
error growth trips the gate) — a numerically-broken kernel change can't land
silently. Errors are floored at ``ERR_FLOOR`` so a kernel that happens to be
bit-exact against its reference still yields a meaningful ratio baseline.
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decision_scan.ops import decision_scan
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.lindley_scan.ops import lindley_scan
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_reference
from repro.kernels.ssm_scan.ops import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_reference

from .common import emit, timed

KEY = jax.random.PRNGKey(0)

ERR_FLOOR = 1e-9  # measurement floor for bit-exact kernels (keeps ratios finite)


def _err(out, ref) -> float:
    return max(float(jnp.max(jnp.abs(out - ref))), ERR_FLOOR)


def kernel_rows(out_dir: Path | None = None) -> dict:
    ks = jax.random.split(KEY, 5)
    report: dict[str, dict] = {}

    def record(name: str, us: float, err: float) -> None:
        report[name] = {"us_per_call": us, "max_abs_err": err}
        emit(f"kernel_{name}", us, f"max_err={err:.2e}")

    # flash attention
    q = jax.random.normal(ks[0], (1, 256, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.float32)
    ref, us = timed(lambda: jax.block_until_ready(flash_attention(q, k, v, impl="xla")))
    out = flash_attention(q, k, v, impl="interpret", blk_q=64, blk_k=64)
    record("flash_attention", us, _err(out, ref))

    # decode attention
    qd = jax.random.normal(ks[0], (2, 1, 8, 64), jnp.float32)
    kc = jax.random.normal(ks[1], (2, 512, 2, 64), jnp.float32)
    vc = jax.random.normal(ks[2], (2, 512, 2, 64), jnp.float32)
    ref, us = timed(lambda: jax.block_until_ready(decode_attention(qd, kc, vc, jnp.int32(511), impl="xla")))
    out = decode_attention(qd, kc, vc, jnp.int32(511), impl="interpret", blk_k=128)
    record("decode_attention", us, _err(out, ref))

    # ssm scan
    B, T, D, N = 2, 128, 128, 8
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, T, D))) * 0.1
    Bc = jax.random.normal(ks[1], (B, T, N))
    Cc = jax.random.normal(ks[2], (B, T, N))
    u = jax.random.normal(ks[3], (B, T, D))
    A = -jnp.exp(jax.random.normal(ks[4], (D, N)) * 0.5)
    ref, us = timed(lambda: jax.block_until_ready(ssm_scan_reference(dt, Bc, Cc, u, A)[0]))
    out = ssm_scan(dt, Bc, Cc, u, A, impl="interpret", blk_t=32, blk_d=128)
    record("ssm_scan", us, _err(out, ref))

    # rmsnorm
    x = jax.random.normal(ks[0], (8, 128, 512), jnp.float32)
    sc = jax.random.normal(ks[1], (512,)) * 0.1
    ref, us = timed(lambda: jax.block_until_ready(rmsnorm_reference(x, sc)))
    out = rmsnorm(x, sc, impl="interpret")
    record("rmsnorm", us, _err(out, ref))

    # lindley scan (the fleet simulator's per-station recurrence)
    rng = np.random.default_rng(0)
    arr = jnp.asarray(np.cumsum(rng.exponential(0.1, (16, 1024)), axis=1), jnp.float32)
    svc = jnp.asarray(rng.exponential(0.05, (16, 1024)), jnp.float32)
    ref, us = timed(lambda: jax.block_until_ready(lindley_scan(arr, svc, impl="xla")))
    out = lindley_scan(arr, svc, impl="interpret", blk_b=16, blk_t=256)
    record("lindley_scan", us, _err(out, ref))

    # decision scan (the cluster simulator's per-epoch staggered decide step)
    costs = jnp.asarray(rng.exponential(0.05, (256, 16, 5)), jnp.float32)
    coh = jnp.asarray(np.arange(16) % 4, jnp.int32)
    ref, us = timed(lambda: jax.block_until_ready(
        decision_scan(costs, coh, hysteresis=0.15, stagger=4, impl="xla")))
    out = decision_scan(costs, coh, hysteresis=0.15, stagger=4,
                        impl="interpret", blk_n=16, blk_t=64)
    record("decision_scan", us, _err(out, ref))

    if out_dir is not None:
        (out_dir / "BENCH_kernels.json").write_text(json.dumps(report, indent=2))
    return report
