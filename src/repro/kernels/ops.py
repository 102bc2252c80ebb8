"""Jit'd public wrappers around the Pallas kernels.

``use_pallas(True)`` (or RunConfig.use_pallas / SpreezeConfig.use_pallas)
flips the model stack's attention / SSD / norm hot spots and the
replay-ring path from the jnp oracle form to these kernels. The
``interpret`` flag is no longer hardcoded: every wrapper resolves it
from the backend at trace time (``_compat.interpret_default`` — Mosaic
on TPU, interpreter on this CPU container) and threads it through the
``pallas_call`` sites.

The ``*_sharded`` wrappers graduate the replay kernels to the
``("ac","batch")`` trainer mesh: each batch group runs the window-aware
kernel (``kernels.replay_ops``) on its local ring shard inside
``shard_map`` — the ring write keeps only in-window rows, the gather
zero-fills out-of-window rows and combines the partial results with a
``psum_scatter`` over the batch axes, the PER score/scatter passes stay
fully group-local. This is what lets the sharded fused megastep execute
Pallas instead of silently falling back to jnp scatter/gather.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

from repro.analysis.hlolint.contract import (CollectiveContract,
                                             CollectiveRule,
                                             EntrypointContract)
from repro.distributed.sharding import (MeshRules, batch_axes,
                                        batch_group_index)
from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import replay_ops as _replay
from repro.kernels import rmsnorm as _rms
from repro.kernels import ssd_scan as _ssd
from repro.kernels._compat import interpret_default

# re-exported jnp oracles (single source of truth for both paths)
per_scores_ref = _replay.per_scores_ref
per_topk_ref = _replay.per_topk_ref
merge_topk_candidates = _replay.merge_topk_candidates

_USE_PALLAS: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "use_pallas", default=False)


def pallas_enabled() -> bool:
    return _USE_PALLAS.get()


@contextlib.contextmanager
def use_pallas(on: bool = True):
    tok = _USE_PALLAS.set(on)
    try:
        yield
    finally:
        _USE_PALLAS.reset(tok)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, block_q: int = 128,
                    block_k: int = 128) -> jax.Array:
    """(B,Sq,H,d) x (B,Sk,KV,d)^2 -> (B,Sq,H,d)."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret_default())


@functools.partial(jax.jit, static_argnames=("block_k",))
def decode_attention(q, k_cache, v_cache, valid_len, *,
                     block_k: int = 256) -> jax.Array:
    """(B,H,d) x (B,S,KV,d)^2 -> (B,H,d)."""
    return _dec.decode_attention(q, k_cache, v_cache, valid_len,
                                 block_k=block_k,
                                 interpret=interpret_default())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dtA, B_, C_, *, chunk: int = 64
             ) -> Tuple[jax.Array, jax.Array]:
    """(B,S,H,P) SSD forward -> (y, final_state)."""
    return _ssd.ssd_scan(x, dtA, B_, C_, chunk=chunk,
                         interpret=interpret_default())


@functools.partial(jax.jit, static_argnames=("eps", "block_rows"))
def rmsnorm(x, weight, *, eps: float = 1e-6, block_rows: int = 256
            ) -> jax.Array:
    return _rms.rmsnorm(x, weight, eps=eps, block_rows=block_rows,
                        interpret=interpret_default())


# --------------------------------------------------------------------------- #
# replay ring: single-device wrappers
# --------------------------------------------------------------------------- #

@jax.jit
def ring_write(data, batch, ptr) -> jax.Array:
    """Blocked replay-ring scatter of (n, ...) rows at (ptr + i) %
    capacity. In place via input/output aliasing when the caller donates
    ``data`` (``add_batch_jit`` and the fused megastep do)."""
    return _replay.ring_write(data, batch, ptr)


@jax.jit
def ring_gather(data, idx) -> jax.Array:
    """Blocked batched random row gather from the replay ring."""
    return _replay.ring_gather(data, idx)


@functools.partial(jax.jit, static_argnames=("alpha",))
def per_scores(priorities, gumbel, alpha: float) -> jax.Array:
    """Gumbel-top-k PER sampling scores (empty slots -> -inf)."""
    return _replay.per_scores(priorities, gumbel, alpha)


@jax.jit
def priority_scatter(priorities, idx, values) -> jax.Array:
    """priorities[idx] = values (PER re-prioritization scatter)."""
    return _replay.priority_scatter(priorities, idx, values)


@functools.partial(jax.jit, static_argnames=("alpha", "k"))
def per_topk(priorities, gumbel, alpha: float, k: int):
    """PER score pass + threshold top-k selection. -> (scores (k,),
    global indices (k,), priorities at the indices (k,))."""
    return _replay.per_topk(priorities, gumbel, alpha, k)


# --------------------------------------------------------------------------- #
# replay ring: shard_map wrappers over the ("ac","batch") trainer mesh
# --------------------------------------------------------------------------- #

# hlolint COLLECTIVE_CONTRACT fragments: the wire budget of each sharded
# wrapper, declared next to the ops that emit the traffic. The megastep
# contract in core/pipeline.py composes these — dims are expressions
# over the probe's symbol table (capacity/batch/groups/k), and the
# invariant they encode is PR-4's: replay traffic is NEVER
# capacity-proportional.
RING_GATHER_COLLECTIVES = (
    # psum_scatter hands each group its (batch//groups) slice of the
    # summed partial gathers (trailing dims = the row payload)
    CollectiveRule("reduce-scatter", ("batch//groups", "...")),
)
PER_TOPK_COLLECTIVES = (
    # the (groups*k,) candidate merge — the ONLY cross-group PER traffic
    # (score, index and priority gathers, one all-gather each)
    CollectiveRule("all-gather", ("groups*k",)),
)

HLOLINT_CONTRACTS = (
    EntrypointContract(
        name="per_topk_sharded", module=__name__, min_devices=8,
        collectives=CollectiveContract(allow=PER_TOPK_COLLECTIVES,
                                       max_elems="capacity")),
    EntrypointContract(
        name="ring_gather_sharded", module=__name__, min_devices=8,
        collectives=CollectiveContract(allow=RING_GATHER_COLLECTIVES,
                                       max_elems="capacity")),
)


def _row_spec(rules: MeshRules, ndim: int) -> P:
    """(rows, ...) leaf: rows over the batch axes, rest replicated."""
    return P(rules.batch, *([None] * (ndim - 1)))


def ring_write_sharded(data, batch, ptr, rules: MeshRules) -> jax.Array:
    """Mesh-native ring write: each batch group gets the full batch and
    runs the window-aware blocked kernel on its contiguous ring shard,
    keeping only the rows whose slot falls in its window. No cross-group
    traffic beyond the batch broadcast GSPMD already pays."""
    _replay.TRACE_COUNTS["shard:ring_write"] += 1
    cap = data.shape[0]
    groups = rules.axis_size(rules.batch)
    rows_local = cap // groups
    spec = _row_spec(rules, data.ndim)

    def local(d, b, p):
        lo = batch_group_index(rules) * rows_local
        return _replay.ring_write(d, b, p, capacity=cap, window_start=lo)

    return jax.shard_map(local, mesh=rules.mesh,
                         in_specs=(spec, P(), P()), out_specs=spec,
                         check_vma=False)(data, batch, ptr)


def ring_gather_sharded(data, idx, rules: MeshRules) -> jax.Array:
    """Mesh-native gather: each group gathers the in-window subset of
    the (global) indices from its local shard with zeros elsewhere; a
    ``psum_scatter`` over the batch axes sums the partials and hands
    every group exactly its slice of the output rows — the minimal
    all-to-all, and the per-group communication pattern the ROADMAP's
    RDMA-local PER sampling needs."""
    _replay.TRACE_COUNTS["shard:ring_gather"] += 1
    groups = rules.axis_size(rules.batch)
    rows_local = data.shape[0] // groups
    axes = batch_axes(rules)
    spec = _row_spec(rules, data.ndim)

    def local(d, i):
        lo = batch_group_index(rules) * rows_local
        part = _replay.ring_gather(d, i, window_start=lo)
        return jax.lax.psum_scatter(part, axes, scatter_dimension=0,
                                    tiled=True)

    return jax.shard_map(local, mesh=rules.mesh,
                         in_specs=(spec, P()), out_specs=spec,
                         check_vma=False)(data, idx)


def per_scores_sharded(priorities, gumbel, alpha: float,
                       rules: MeshRules) -> jax.Array:
    """Mesh-native PER scores: elementwise, so each group scores its
    local priority shard against its slice of the Gumbel noise."""
    _replay.TRACE_COUNTS["shard:per_scores"] += 1
    spec = P(rules.batch)

    def local(p, g):
        return _replay.per_scores(p, g, alpha)

    return jax.shard_map(local, mesh=rules.mesh,
                         in_specs=(spec, spec), out_specs=spec,
                         check_vma=False)(priorities, gumbel)


def per_topk_sharded(priorities, gumbel, alpha: float, k: int,
                     rules: MeshRules):
    """Mesh-native two-phase PER selection (the ROADMAP's RDMA-local
    sampling): each batch group runs ``per_topk`` (scores + threshold
    top-k) on its local priority shard (window offset = its first global ring
    slot) and emits k candidates ``(score, global_idx, priority)``; an
    ``all_gather`` of the ``(groups * k,)`` candidates over the batch
    axes — the ONLY cross-group traffic, never capacity-proportional —
    feeds the fixed-group-order merge, which every group evaluates
    identically, so the selected index vector comes back replicated and
    the downstream gather/scatter stay group-local. The all_gather's
    concatenation order over the axis tuple is row-major, matching
    ``batch_group_index``, which is what pins the merge's tie order and
    makes the draw layout-invariant."""
    _replay.TRACE_COUNTS["shard:per_topk"] += 1
    groups = rules.axis_size(rules.batch)
    rows_local = priorities.shape[0] // groups
    axes = batch_axes(rules)
    spec = P(rules.batch)

    def local(p, g):
        lo = batch_group_index(rules) * rows_local
        cand = _replay.per_topk(p, g, alpha, k, window_start=lo)
        return _replay.merge_topk_candidates(
            *(jax.lax.all_gather(c, axes, axis=0, tiled=True)
              for c in cand), k)

    return jax.shard_map(local, mesh=rules.mesh,
                         in_specs=(spec, spec),
                         out_specs=(P(), P(), P()),
                         check_vma=False)(priorities, gumbel)


def priority_scatter_sharded(priorities, idx, values,
                             rules: MeshRules) -> jax.Array:
    """Mesh-native PER re-prioritization: every group applies the
    in-window subset of the sampled-index updates to its own shard —
    fully local, no collective."""
    _replay.TRACE_COUNTS["shard:priority_scatter"] += 1
    groups = rules.axis_size(rules.batch)
    rows_local = priorities.shape[0] // groups
    spec = P(rules.batch)

    def local(p, i, v):
        lo = batch_group_index(rules) * rows_local
        return _replay.priority_scatter(p, i, v, window_start=lo)

    return jax.shard_map(local, mesh=rules.mesh,
                         in_specs=(spec, P(), P()), out_specs=spec,
                         check_vma=False)(priorities, idx, values)
