"""Staggered-cohort offload decisions over epochs as a Pallas kernel.

The cluster simulator's per-epoch decision step is, per client, an argmin
over the stacked (on-device | edges) cost row with on-device winning ties,
a relative-improvement hysteresis check against the previously chosen
target's CURRENT cost, and a cohort gate (client i re-decides only when
``t % stagger == i % stagger``). Sequential in the epoch axis (the previous
choice is the carry), embarrassingly parallel in the client axis — the same
shape as the Lindley kernel next door, so the same state-resident pattern
applies: each grid cell keeps a (1, blk_n) row of previous choices in
VMEM scratch for the whole epoch sweep and streams (blk_t, e1, blk_n) cost
tiles through.

Cost tables arrive time-major ``(T, N, E+1)`` (column 0 = on-device, the
cluster convention) and are laid out ``(T, E+1, N)``: clients on lanes, the
tiny target axis on sublanes, epochs on the untiled leading axis, so each
step reads one whole (E+1, blk_n) slab and writes one whole row of the
``(T, N)`` output (a dynamic sublane index, which Mosaic accepts). On TPU
``blk_n`` must be a multiple of 128 or the whole client axis. Epochs are
innermost ("arbitrary") so the choice carry persists across t-blocks; the
client axis is "parallel".
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decision_scan_kernel", "decision_scan_pallas"]

ON_DEVICE = -1  # target index convention (repro.core.manager.ON_DEVICE)


def _compiler_params(grid_len: int):
    sem = ("parallel",) * (grid_len - 1) + ("arbitrary",)
    return pltpu.CompilerParams(dimension_semantics=sem)


def decision_scan_kernel(
    h_ref,  # (1, 1) SMEM — hysteresis fraction
    costs_ref,  # (blk_t, e1, blk_n) stacked per-target costs
    cohort_ref,  # (1, blk_n) int32 — client's decision cohort
    c_ref,  # (blk_t, blk_n) int32 choices out
    prev_ref,  # scratch (1, blk_n) int32 — previous choice per client
    *,
    blk_t: int,
    stagger: int,
):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        prev_ref[...] = jnp.full_like(prev_ref, ON_DEVICE)

    e1 = costs_ref.shape[1]
    h = h_ref[0, 0]
    cohort = cohort_ref[...]  # (1, blk_n)

    def step(t, prev):
        tg = it * blk_t + t  # global epoch index
        costs_t = costs_ref[t]  # (e1, blk_n)
        # first-argmin over targets (a strict < keeps ties on the lowest
        # index, i.e. on-device), gathering the previous target's CURRENT
        # cost along the way
        predicted = costs_t[0:1]
        choice = jnp.full_like(prev, ON_DEVICE)
        prev_t = jnp.where(prev == ON_DEVICE, predicted, 0.0)
        for j in range(1, e1):
            c_j = costs_t[j:j + 1]
            better = c_j < predicted
            predicted = jnp.where(better, c_j, predicted)
            choice = jnp.where(better, j - 1, choice)
            prev_t = jnp.where(prev == j - 1, c_j, prev_t)
        keep = (
            (tg >= stagger)
            & (h > 0.0)
            & (choice != prev)
            & jnp.isfinite(prev_t)
            & (predicted > (1.0 - h) * prev_t)
        )
        decided = jnp.where(keep, prev, choice)
        new = jnp.where(cohort == tg % stagger, decided, prev).astype(jnp.int32)
        c_ref[pl.dslice(t, 1), :] = new
        return new

    prev_ref[...] = jax.lax.fori_loop(0, blk_t, step, prev_ref[...])


def decision_scan_pallas(
    costs: jax.Array,  # (T, N, E+1) stacked costs, column 0 = on-device
    cohort: jax.Array,  # (N,) int32
    *,
    hysteresis: float = 0.0,
    stagger: int = 1,
    blk_n: int = 128,
    blk_t: int = 128,
    interpret: bool = False,
):
    """(T, N) int32 choice trajectory (ON_DEVICE or an edge index)."""
    t, n, e1 = costs.shape
    blk_n = min(blk_n, n)
    blk_t = min(blk_t, t)
    pad_n = (-n) % blk_n
    pad_t = (-t) % blk_t
    # padded epochs run after every real one and padded clients are whole
    # extra columns — both are sliced off below, values irrelevant
    cm = jnp.pad(jnp.swapaxes(costs, 1, 2), ((0, pad_t), (0, 0), (0, pad_n)))
    co = jnp.pad(cohort.astype(jnp.int32)[None, :], ((0, 0), (0, pad_n)))
    tp, _, np_ = cm.shape
    grid = (np_ // blk_n, tp // blk_t)
    h = jnp.asarray(hysteresis, cm.dtype).reshape(1, 1)
    out = pl.pallas_call(
        functools.partial(decision_scan_kernel, blk_t=blk_t, stagger=stagger),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((blk_t, e1, blk_n), lambda i, it: (it, 0, i)),
            pl.BlockSpec((1, blk_n), lambda i, it: (0, i)),
        ],
        out_specs=pl.BlockSpec((blk_t, blk_n), lambda i, it: (it, i)),
        out_shape=jax.ShapeDtypeStruct((tp, np_), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, blk_n), jnp.int32)],
        compiler_params=_compiler_params(len(grid)),
        interpret=interpret,
    )(h, cm, co)
    return out[:t, :n]
