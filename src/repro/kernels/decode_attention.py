"""Flash-decode Pallas kernel: one query token vs a long KV cache.

Grid: (batch, q_head, cache_blocks) — the cache-length axis is the
"arbitrary" accumulation axis, so the kernel streams (block_k x d) cache
tiles HBM->VMEM and maintains a running (max, sum, acc) online softmax in
VMEM scratch. This is the hot spot for decode_32k / long_500k: arithmetic
intensity is O(1) FLOP/byte, so the roofline term is pure HBM bandwidth
and the kernel's job is to never re-read the cache.

``valid_len`` masks ring-buffer slots that are not yet written (decode
warm-up) — it arrives as a scalar-prefetch operand in SMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._compat import resolve_interpret

NEG_INF = -1e30


def _decode_kernel(vl_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, block_k: int):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0, :].astype(jnp.float32)             # (d,)
    k = k_ref[0, :, 0, :].astype(jnp.float32)          # (block_k, d)
    v = v_ref[0, :, 0, :].astype(jnp.float32)

    s = (k @ q) * scale                                # (block_k,)
    kpos = ki * block_k + jax.lax.iota(jnp.int32, block_k)
    mask = kpos < vl_ref[0]
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[0, 0]
    m_new = jnp.maximum(m_prev, s.max())
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_scr[0, 0] = alpha * l_scr[0, 0] + p.sum()
    acc_scr[0, :] = alpha * acc_scr[0, :] + p @ v
    m_scr[0, 0] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0, 0, :] = (acc_scr[0, :]
                          / jnp.maximum(l_scr[0, 0], 1e-30)
                          ).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, valid_len, *,
                     scale: Optional[float] = None, block_k: int = 256,
                     interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, H, d); caches: (B, S, KV, d); valid_len: scalar int32 —
    cache slots [0, valid_len) attend. Returns (B, H, d)."""
    B, H, d = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = d ** -0.5 if scale is None else scale

    block_k = min(block_k, S)
    pk = (-S) % block_k
    if pk:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nk = k_cache.shape[1] // block_k
    vl = jnp.minimum(jnp.asarray(valid_len, jnp.int32), S).reshape((1,))

    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nk),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda b, h, ki, vl: (b, h, 0)),
            pl.BlockSpec((1, block_k, 1, d),
                         lambda b, h, ki, vl, G=G: (b, ki, h // G, 0)),
            pl.BlockSpec((1, block_k, 1, d),
                         lambda b, h, ki, vl, G=G: (b, ki, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda b, h, ki, vl: (b, h, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(vl, q, k_cache, v_cache)
    return out
