"""Fused RMSNorm for TPU: one HBM read, fp32 reduction in VMEM, one write.

Rows stream through in (blk_rows, d) tiles; the scale vector is resident.
Fusing the normalise+scale epilogue halves HBM traffic vs. the unfused pair —
the memory-bound term this attacks shows up in every decode-cell roofline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rmsnorm_kernel", "rmsnorm_pallas"]


def _compiler_params(grid_len: int):
    return pltpu.CompilerParams(dimension_semantics=("parallel",) * grid_len)


def rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (blk, d)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * (1.0 + s_ref[...].astype(jnp.float32))[None, :]).astype(o_ref.dtype)


def rmsnorm_pallas(x: jax.Array, scale: jax.Array, eps: float = 1e-6, *, blk_rows: int = 256, interpret: bool = False):
    orig_shape = x.shape
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    blk = min(blk_rows, n)
    pad = (-n) % blk
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    grid = (xf.shape[0] // blk,)
    out = pl.pallas_call(
        functools.partial(rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((blk, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((blk, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        compiler_params=_compiler_params(len(grid)),
        interpret=interpret,
    )(xf, scale)
    if pad:
        out = out[:n]
    return out.reshape(orig_shape)
