"""Prioritized experience replay (Schaul et al. 2016), device-resident.

The paper's strongest baseline (RLlib APE-X) couples many samplers with
prioritized replay; this module provides the same capability on the
Spreeze shared-memory pool so the comparison is apples-to-apples inside
one framework.

TPU adaptation: the classic CPU sum-tree is pointer-chasing and
host-bound. Here priorities live in HBM next to the data and sampling is
the Gumbel-top-k trick — ``argtop_k(log p_i + G_i)`` draws k indices
WITHOUT replacement proportionally to p_i in one fused vectorized pass
(O(N) work, no tree, no host round-trip), which is the bandwidth-friendly
form for an accelerator.

Empty-slot semantics: unwritten rows carry priority 0 and are masked to
a TRUE ``-inf`` score (a finite floor like ``log(1e-12)`` loses to
Gumbel noise and silently feeds all-zero rows into the update — the
original bug). Draws beyond the live-row count cycle through the live
draws (sampling with replacement once the pool is exhausted), and
importance weights, normalized by the batch max, depend only on the
drawn rows' priorities (the pool-wide normalizer cancels), so the empty
capacity slots of a partially-filled pool cannot bias them.

Two-phase selection (group-local PER): index selection routes through
``_select`` in every mode — each batch group (one group when meshless)
runs a top-k over its OWN priority shard and only ``(groups * k,)``
candidates cross the batch axis for the merge; no globally assembled
``(capacity,)`` score vector exists. Under ``use_pallas`` the per-group
pass is ``per_topk`` (the score pass, then an exact top-k by threshold
that sorts only the k survivors) shard_map'd over the mesh batch axes
(``kernels.ops.per_topk_sharded``); the jnp oracle is the dense
``per_topk_ref`` (two-phase with a single group — bit-identical, since
the merge in fixed group order with stable ties IS the dense top-k over
live rows). That identity is the layout-invariance guarantee: given the
same pool state and key, PER draws the same batch on (1,1), (1,8), or
(2,4) meshes, pallas or jnp (``jax_threefry_partitionable`` keeps the
Gumbel noise itself layout-invariant). The re-prioritization scatter and
the importance-weight priorities (carried with the candidates) are
likewise group-local — no PER op moves capacity-proportional data
across groups (``benchmarks/roofline.py`` asserts it on the lowered
HLO).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import current_rules, shard
from repro.kernels import ops as kops
from repro.replay.buffer import (ReplayState, _pallas_keyed_jit,
                                 _per_select_mode, _ring_mode, gather_rows,
                                 init_replay, ring_slots_mask, unpack_rows,
                                 write_plan)


class PrioritizedState(NamedTuple):
    base: ReplayState
    priorities: jax.Array        # (capacity,) f32, 0 for unwritten rows
    max_priority: jax.Array      # scalar f32 — new rows get max (PER paper)


def init_prioritized(capacity: int, specs) -> PrioritizedState:
    return PrioritizedState(
        base=init_replay(capacity, specs),
        priorities=jnp.zeros((capacity,), jnp.float32),
        max_priority=jnp.ones((), jnp.float32))


def add_batch(state: PrioritizedState, batch: Dict[str, jax.Array]
              ) -> PrioritizedState:
    """New experience enters at max priority (ensures each row is seen)."""
    from repro.replay.buffer import add_batch as base_add
    n = next(iter(batch.values())).shape[0]
    cap = state.priorities.shape[0]
    # same ring slots as base_add's data write, incl. oversized-write drop
    ptr0, keep = write_plan(state.base.ptr, n, cap)
    # priorities live row-aligned with the data: same batch-axis shard
    pri = shard(jnp.where(ring_slots_mask(cap, ptr0, keep),
                          state.max_priority, state.priorities), "batch")
    return PrioritizedState(base=base_add(state.base, batch),
                            priorities=pri,
                            max_priority=state.max_priority)


def _select(priorities: jax.Array, gumbel: jax.Array, alpha: float,
            k: int):
    """Two-phase PER index selection -> (scores (k,), indices (k,),
    priorities at the indices (k,)).

    Phase 1 is group-local: each batch group top-k's its own priority
    shard (``per_topk``'s score pass + threshold top-k under
    ``use_pallas``, the dense ``per_topk_ref`` oracle otherwise). Phase
    2 merges the ``(groups * k,)`` candidates in fixed group order
    (``merge_topk_candidates``) — with a single group the merge is the
    identity, so every mode computes the same dense top-k over live
    rows and PER draws are layout-invariant. ``"shard"`` requires each
    group's shard to hold >= k rows (``buffer._per_select_mode``)."""
    mode = _per_select_mode(priorities.shape[0], k)
    if mode == "pallas":
        return kops.per_topk(priorities, gumbel, alpha, k)
    if mode == "shard":
        return kops.per_topk_sharded(priorities, gumbel, alpha, k,
                                     current_rules())
    return kops.per_topk_ref(priorities, gumbel, alpha, k)


def sample(state: PrioritizedState, key, batch_size: int, *,
           alpha: float = 0.6, beta: float = 0.4
           ) -> Tuple[Dict[str, jax.Array], jax.Array, jax.Array]:
    """-> (batch, indices, importance weights (normalized to max 1)).

    Gumbel-top-k over alpha-annealed log-priorities == sampling without
    replacement proportional to p^alpha. Unwritten slots (p == 0) score
    a true ``-inf`` and can never be drawn; if ``batch_size`` exceeds
    the live-row count the surplus draws cycle through the live draws
    (replacement kicks in only once the pool is exhausted). The pool
    must hold at least one written row (warmup guarantees it).

    Selection is the two-phase group-local top-k (``_select``) — the
    drawn indices are identical across mesh layouts and across the
    pallas/jnp paths. Every capacity-sized intermediate here is
    elementwise on (or gathered group-locally from) the sharded
    priority vector, so sampling adds no capacity-proportional
    cross-group traffic.
    """
    g = shard(-jnp.log(-jnp.log(
        jax.random.uniform(key, state.priorities.shape,
                           minval=1e-12, maxval=1.0))), "batch")
    _, idx, pri_sel = _select(state.priorities, g, alpha, batch_size)
    # every live row outranks every -inf empty slot, so draws past the
    # live count are garbage — wrap them onto the live draws
    live = state.priorities > 0.0
    n_live = jnp.maximum(jnp.sum(live.astype(jnp.int32)), 1)
    draw = jnp.arange(batch_size) % n_live
    idx, pri_sel = jnp.take(idx, draw), jnp.take(pri_sel, draw)
    batch = unpack_rows(state.base.layout,
                        gather_rows(state.base.rows, idx))

    # importance weights: w_i = (N * P(i))^-beta with P(i) = p_i / sum p
    # over the written rows, normalized by the batch max. N and the sum
    # cancel in the normalization, so w_i = (min_j p_j / p_i)^beta over
    # the drawn rows: no capacity-sized reduction, whose summation
    # order (and so its last bit) would depend on how XLA fuses it. The
    # sampled rows' priorities ride along with the selection candidates:
    # indexing the sharded (capacity,) vector would all-gather it.
    p_sel = jnp.maximum(pri_sel, 1e-12) ** alpha
    w = (jnp.min(p_sel) / p_sel) ** beta
    return batch, idx, w


def update_priorities(state: PrioritizedState, idx, td_errors,
                      eps: float = 1e-3) -> PrioritizedState:
    """Set sampled rows' priorities to |TD error| + eps (PER eq. 1) via
    the Pallas scatter kernel (group-local under shard_map) or the jnp
    scatter, per the trace-time dispatch."""
    pri_new = jnp.abs(td_errors) + eps
    mode = _ring_mode(state.priorities.shape[0])
    if mode == "pallas":
        pri = kops.priority_scatter(state.priorities, idx, pri_new)
    elif mode == "shard":
        pri = kops.priority_scatter_sharded(state.priorities, idx,
                                            pri_new, current_rules())
    else:
        pri = state.priorities.at[idx].set(pri_new)
    pri = shard(pri, "batch")
    return PrioritizedState(
        base=state.base, priorities=pri,
        max_priority=jnp.maximum(state.max_priority, jnp.max(pri_new)))


_add_batch_jit = _pallas_keyed_jit(add_batch)


def add_batch_jit(state: PrioritizedState, batch) -> PrioritizedState:
    from repro.replay.buffer import _ring_trace_key
    return _add_batch_jit(_ring_trace_key())(state, batch)
