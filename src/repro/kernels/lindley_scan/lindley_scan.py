"""Batched Lindley recursion (k=1 FCFS departures) as a Pallas kernel.

The fleet simulator's hot loop is the per-station recurrence
``dep_i = max(arr_i, dep_{i-1}) + svc_i`` — sequential in the job axis,
embarrassingly parallel in the scenario axis. The XLA lowering of the
equivalent ``lax.scan`` re-reads the carry from HBM every step; here each
grid cell holds a (1, blk_b) row of scenario clocks in VMEM for the whole
job sweep and streams the (blk_t, blk_b) arrival/service tiles through —
the same state-resident pattern as the ssm_scan kernel next door.

The kernel works time-major: jobs on sublanes, scenarios on lanes, so each
step reads and writes one whole row (a dynamic sublane index, which Mosaic
accepts) rather than one lane column (which it refuses unless the index is a
provable multiple of 128). On TPU ``blk_b`` must be a multiple of 128 or the
whole batch. Time is innermost ("arbitrary") so the clock carry persists
across t-blocks; the batch axis is "parallel".
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["lindley_scan_kernel", "lindley_scan_pallas"]


def _compiler_params(grid_len: int):
    sem = ("parallel",) * (grid_len - 1) + ("arbitrary",)
    return pltpu.CompilerParams(dimension_semantics=sem)


def lindley_scan_kernel(
    a_ref,  # (blk_t, blk_b) arrivals, time-major
    s_ref,  # (blk_t, blk_b) services
    d_ref,  # (blk_t, blk_b) departures out
    clk_ref,  # scratch (1, blk_b) — last departure per scenario column
    *,
    blk_t: int,
):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        clk_ref[...] = jnp.full_like(clk_ref, -jnp.inf)

    def step(t, clk):
        row = pl.dslice(t, 1)
        dep = jnp.maximum(a_ref[row, :], clk) + s_ref[row, :]  # (1, blk_b)
        d_ref[row, :] = dep.astype(d_ref.dtype)
        return dep

    clk_ref[...] = jax.lax.fori_loop(0, blk_t, step, clk_ref[...])


def lindley_scan_pallas(
    arrivals: jax.Array,  # (B, T), non-decreasing along T per row
    services: jax.Array,  # (B, T)
    *,
    blk_b: int = 128,
    blk_t: int = 512,
    interpret: bool = False,
):
    """Departure times of B independent single-server FCFS stations."""
    b, t = arrivals.shape
    blk_b = min(blk_b, b)
    blk_t = min(blk_t, t)
    pad_b = (-b) % blk_b
    pad_t = (-t) % blk_t
    # padded jobs arrive at +0 service after the real ones; the padded job
    # rows and scenario columns are sliced off below, so values are irrelevant
    at = jnp.pad(arrivals.T, ((0, pad_t), (0, pad_b)))
    st = jnp.pad(services.T, ((0, pad_t), (0, pad_b)))
    tp, bp = at.shape
    grid = (bp // blk_b, tp // blk_t)
    tile = pl.BlockSpec((blk_t, blk_b), lambda ib, it: (it, ib))
    out = pl.pallas_call(
        functools.partial(lindley_scan_kernel, blk_t=blk_t),
        grid=grid,
        in_specs=[tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((tp, bp), arrivals.dtype),
        scratch_shapes=[pltpu.VMEM((1, blk_b), arrivals.dtype)],
        compiler_params=_compiler_params(len(grid)),
        interpret=interpret,
    )(at, st)
    return out[:t, :b].T
