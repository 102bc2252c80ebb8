"""Fused RMSNorm Pallas kernel (memory-bound fusion example).

One pass over (rows x d_model) VMEM tiles: reduce, rsqrt, scale — the
read-once/write-once pattern that matters for the norm-heavy decode path
(every layer runs two of these per token). Grid over row blocks; the
weight vector is a replicated VMEM operand.
"""
from __future__ import annotations

import functools

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._compat import resolve_interpret


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                 # (block_r, D)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


def rmsnorm(x, weight, *, eps: float = 1e-6, block_rows: int = 256,
            interpret: Optional[bool] = None) -> jax.Array:
    """x: (..., D); weight: (D,)."""
    orig_shape = x.shape
    D = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, D)
    block_rows = max(1, min(block_rows, rows))
    pr = (-rows) % block_rows
    if pr:
        x2 = jnp.pad(x2, ((0, pr), (0, 0)))
    nr = x2.shape[0] // block_rows

    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda r: (r, 0)),
            pl.BlockSpec((D,), lambda r: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=resolve_interpret(interpret),
    )(x2, weight)
    if pr:
        out = out[:rows]
    return out.reshape(orig_shape)
