"""Blocked online-softmax (flash) attention Pallas kernel for TPU.

Grid: (batch, q_head, q_blocks, k_blocks); the k-block dimension is the
innermost "arbitrary" axis so each (b, h, qi) cell accumulates its online
softmax in VMEM scratch across k blocks. GQA never materializes repeated
KV: the k/v BlockSpec index map folds ``h -> h // group_size``. Causal and
sliding-window masking are positional, and fully-masked k blocks write
nothing (the mask zeroes them; block-level skipping is a lowering-time
optimization XLA cannot see — recorded in EXPERIMENTS §Perf).

Block shapes are (block_q x d) / (block_k x d) VMEM tiles — d is the
head_dim lane axis (<=128 for every assigned arch, MXU-aligned).
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_k: int, q_offset: int, k_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, :, 0, :].astype(jnp.float32)          # (block_q, d)
    k = k_ref[0, :, 0, :].astype(jnp.float32)          # (block_k, d)
    v = v_ref[0, :, 0, :].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = kpos < k_len                                # tail padding
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                # (block_q, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)                        # kill -inf - -inf
    l_scr[...] = alpha * l_scr[...] + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, :, 0, :] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, Sq, H, d); k/v: (B, Sk, KV, d); H % KV == 0.
    q positions are aligned to the end of k (prefill/train: Sq == Sk)."""
    B, Sq, H, d = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = d ** -0.5 if scale is None else scale
    q_offset = Sk - Sq

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq = q.shape[1] // block_q
    nk = k.shape[1] // block_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, q_offset=q_offset, k_len=Sk)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, 1, d),
                         lambda b, h, qi, ki: (b, qi, h, 0)),
            pl.BlockSpec((1, block_k, 1, d),
                         lambda b, h, qi, ki, G=G: (b, ki, h // G, 0)),
            pl.BlockSpec((1, block_k, 1, d),
                         lambda b, h, qi, ki, G=G: (b, ki, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, d),
                               lambda b, h, qi, ki: (b, qi, h, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    if pq:
        out = out[:, :Sq]
    return out
