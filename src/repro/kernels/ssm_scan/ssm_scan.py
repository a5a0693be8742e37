"""Selective scan (Mamba S6) for TPU: state-resident-in-VMEM recurrence.

The XLA lowering of the scan re-reads/re-writes the (B, D, N) state from HBM
every step (a while-loop over dynamic-update-slices). Here the state lives in
VMEM scratch for the whole sweep — the TPU translation of Mamba's
SRAM-resident CUDA kernel [arXiv:2312.00752] — and only the (blk_t x blk_d)
input/output tiles stream through HBM. Grid = (batch, d-block, t-block) with
time innermost ("arbitrary"): scratch h persists across t-blocks; each grid
cell runs a fori_loop over its blk_t steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssm_scan_kernel", "ssm_scan_pallas"]


def _compiler_params(grid_len: int):
    sem = ("parallel",) * (grid_len - 1) + ("arbitrary",)
    return pltpu.CompilerParams(dimension_semantics=sem)


def ssm_scan_kernel(
    dt_ref,  # (1, blk_t, blk_d)
    b_ref,  # (1, blk_t, N)
    c_ref,  # (1, blk_t, N)
    u_ref,  # (1, blk_t, blk_d)
    a_ref,  # (blk_d, N)
    y_ref,  # (1, blk_t, blk_d)
    h_ref,  # scratch (blk_d, N) f32
    *,
    blk_t: int,
):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...].astype(jnp.float32)  # (blk_d, N)

    def step(t, h):
        dt_t = dt_ref[0, t].astype(jnp.float32)  # (blk_d,)
        u_t = u_ref[0, t].astype(jnp.float32)
        b_t = b_ref[0, t].astype(jnp.float32)  # (N,)
        c_t = c_ref[0, t].astype(jnp.float32)
        decay = jnp.exp(dt_t[:, None] * a)  # (blk_d, N)
        h = decay * h + (dt_t * u_t)[:, None] * b_t[None, :]
        y_t = jnp.sum(h * c_t[None, :], axis=1)  # (blk_d,)
        y_ref[pl.dslice(0, 1), pl.dslice(t, 1), :] = y_t[None, None].astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, blk_t, step, h_ref[...])
    h_ref[...] = h


def ssm_scan_pallas(
    dt: jax.Array,  # (B, T, D)
    Bc: jax.Array,  # (B, T, N)
    Cc: jax.Array,  # (B, T, N)
    u: jax.Array,  # (B, T, D)
    A: jax.Array,  # (D, N)
    *,
    blk_t: int = 256,
    blk_d: int = 512,
    interpret: bool = False,
):
    """Returns y (B, T, D) (final state is recovered by the wrapper when
    needed via a short reference tail — the kernel's contract is the output
    sequence, matching the training hot path). On TPU ``blk_d`` must be a
    multiple of 128 or all of D, and ``blk_t`` a multiple of 8 or all of T
    (the block tiling rule)."""
    B, T, D = u.shape
    N = A.shape[1]
    blk_t = min(blk_t, T)
    blk_d = min(blk_d, D)
    assert T % blk_t == 0 and D % blk_d == 0
    nt, nd = T // blk_t, D // blk_d

    kernel = functools.partial(ssm_scan_kernel, blk_t=blk_t)
    grid = (B, nd, nt)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_t, blk_d), lambda b, id_, it: (b, it, id_)),
            pl.BlockSpec((1, blk_t, N), lambda b, id_, it: (b, it, 0)),
            pl.BlockSpec((1, blk_t, N), lambda b, id_, it: (b, it, 0)),
            pl.BlockSpec((1, blk_t, blk_d), lambda b, id_, it: (b, it, id_)),
            pl.BlockSpec((blk_d, N), lambda b, id_, it: (id_, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_t, blk_d), lambda b, id_, it: (b, it, id_)),
        out_shape=jax.ShapeDtypeStruct((B, T, D), u.dtype),
        scratch_shapes=[pltpu.VMEM((blk_d, N), jnp.float32)],
        compiler_params=_compiler_params(len(grid)),
        interpret=interpret,
    )(dt, Bc, Cc, u, A)
