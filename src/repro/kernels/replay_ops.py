"""Pallas replay-ring kernels: blocked, double-buffered, window-aware (§3.3.2).

The replay pool is Spreeze's shared memory; its hot operations are the
sampler-side ring write (rows land at ``(ptr + i) % capacity``), the
updater-side batched random gather, and — for the APE-X-style PER
comparison — the priority-score pass and the post-update priority
scatter. The first generation of these kernels walked the pool one row
at a time with ``dynamic_slice`` stores; these kernels instead tile the
rows into blocks and pipeline the HBM<->VMEM traffic with
``pltpu.make_async_copy`` double buffering:

* ``ring_write``  — the pool stays in HBM (``pl.ANY``); batch blocks are
  DMA'd into a 2-slot VMEM scratch (block ``b+1`` fetches while block
  ``b`` writes out) and leave as one contiguous VMEM->HBM DMA per block.
  ``input_output_aliases`` pins the pool buffer so the scatter is
  genuinely in place when the caller donates it (``add_batch_jit`` / the
  fused megastep do).
* ``ring_gather`` — a grid over output blocks (the Pallas pipeline
  double-buffers the VMEM out tiles); within a block the random row
  fetches run as a depth-``GATHER_DEPTH`` window of in-flight HBM->VMEM
  DMAs instead of issue-wait-issue-wait.
* ``per_scores`` — blocked elementwise pass over lane-dense
  ``(rows, 128)`` tiles producing the Gumbel-top-k sampling scores for
  the PER pool (empty slots masked to a true -inf).
* ``per_topk`` — PER selection: the ``per_scores`` pass writing
  order-preserving int32 keys, a kernel that finds the k-th largest key
  bit by bit with the keys resident in VMEM, then XLA compacts the k
  rows at or above it and ``top_k`` sorts only those k: nothing sorts
  the window (steps above ``_order_keys``). Under ``shard_map`` each
  batch group emits its local k candidates ``(score, global_idx,
  priority)`` and
  ``merge_topk_candidates`` reduces the ``(groups * k,)`` gathered
  candidates — selection is group-local and the only cross-group PER
  traffic is k candidates per group, never anything proportional to
  capacity. Because the merge runs in a fixed group order with stable
  ties, the two-phase selection is exactly the dense ``top_k`` on live
  rows: PER draws are layout-invariant across mesh shapes (see
  ``replay.prioritized``).
* ``priority_scatter`` — the new |TD|+eps priorities at the sampled
  (arbitrary) indices, as a blocked read-modify-write of the priority
  vector, which stays in HBM.

Mosaic moves only lane-aligned slices, so a row DMA moves one whole
128-lane row: the ring kernels run on the replay's packed
``(capacity, 128)`` records (``replay.buffer``), and the PER kernels
view the ``(capacity,)`` priority vector as ``(capacity / 128, 128)``.

Every kernel takes a **window**: the operand may be a shard covering
global ring slots ``[window_start, window_start + local_rows)`` of a
``capacity``-row pool. Rows that fall outside the window are skipped
(write/scatter) or zero-filled (gather — the shard_map wrapper in
``kernels.ops`` combines the partial gathers with a ``psum_scatter``).
With the default window (the whole pool) the kernels are the
single-device path; under an active ``("ac","batch")`` mesh
``kernels.ops`` wraps them in ``shard_map`` so each batch group runs the
kernel on its local ring shard.

``interpret`` resolves from the backend at trace time (``None`` ->
interpreter off on TPU, on elsewhere); the ``*_ref`` functions are the
jnp oracles the tests compare against, including wraparound and window
cases. ``ring_write_rowloop`` / ``ring_gather_rowloop`` keep the PR-1
row-at-a-time kernels alive as the bench baseline
(``benchmarks/bench_replay_kernels.py``).

``TRACE_COUNTS`` counts kernel *traces* (bumped at trace time, python
side) so tests can prove a compiled program really contains the Pallas
path instead of a silent jnp fallback.
"""
from __future__ import annotations

import collections
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._compat import resolve_interpret

BLOCK_ROWS = 128      # default rows per DMA block (f32 sublane-friendly)
LANES = 128           # f32 lanes of one vreg row
GATHER_DEPTH = 8      # in-flight row DMAs per gather block

TRACE_COUNTS: collections.Counter = collections.Counter()


def reset_trace_counts() -> None:
    TRACE_COUNTS.clear()


def _as2d(x: jax.Array) -> jax.Array:
    """(rows, ...) -> (rows, features); scalars get a singleton feature."""
    return x.reshape(x.shape[0], -1)


# --------------------------------------------------------------------------- #
# ring write: blocked scatter of n rows at (ptr + i) % capacity
# --------------------------------------------------------------------------- #

def _ring_write_kernel(scal_ref, batch_ref, data_ref, out_ref, *,
                       cap: int, n: int, rows_local: int, blk: int):
    """Double-buffered blocked ring write into the window
    [lo, lo + rows_local) of a ``cap``-slot ring.

    Fast path: a full block whose destination run is contiguous (no ring
    wrap) and fully inside the window leaves as ONE VMEM->HBM DMA. The
    (at most one) block that wraps the ring, the (at most two) blocks
    straddling the window edge, and the partial tail block fall back to
    per-row DMAs. Blocks entirely outside the window are neither fetched
    nor written.
    """
    del data_ref                    # aliased with out_ref
    ptr, lo = scal_ref[0], scal_ref[1]
    hi = lo + rows_local
    nb = pl.cdiv(n, blk)

    def rows_in(b):                 # rows this block actually carries
        return jnp.minimum(n - b * blk, blk)

    def start_slot(b):              # global slot of the block's first row
        return jax.lax.rem(ptr + b * blk, cap)

    def need(b):
        """Does block ``b`` touch the window at all? (conservative for
        the wrap block)"""
        s, m = start_slot(b), rows_in(b)
        wrapped = s + m > cap
        disjoint = (s + m <= lo) | (s >= hi)
        return wrapped | ~disjoint

    def body(scratch, fsems, wsems):
        def fetch(slot, b):
            return pltpu.make_async_copy(
                batch_ref.at[pl.ds(b * blk, blk), :],
                scratch.at[slot], fsems.at[slot])

        @pl.when(need(0))
        def _warmup():
            fetch(0, 0).start()

        def loop(b, carry):
            slot = jax.lax.rem(b, 2)

            @pl.when((b + 1 < nb) & need(b + 1))
            def _prefetch():        # overlap next fetch with this write
                fetch(jax.lax.rem(b + 1, 2), b + 1).start()

            @pl.when(need(b))
            def _process():
                fetch(slot, b).wait()
                s, m = start_slot(b), rows_in(b)
                fast = ((m == blk) & (s + blk <= cap)
                        & (s >= lo) & (s + blk <= hi))

                @pl.when(fast)
                def _blocked():
                    w = pltpu.make_async_copy(
                        scratch.at[slot],
                        out_ref.at[pl.ds(s - lo, blk), :],
                        wsems.at[slot])
                    w.start()
                    w.wait()

                @pl.when(~fast)
                def _edges():       # ring wrap / window edge / tail
                    def row(i, c):
                        dest = jax.lax.rem(ptr + b * blk + i, cap) - lo

                        @pl.when((i < m) & (dest >= 0)
                                 & (dest < rows_local))
                        def _row():
                            w = pltpu.make_async_copy(
                                scratch.at[slot, pl.ds(i, 1), :],
                                out_ref.at[pl.ds(dest, 1), :],
                                wsems.at[slot])
                            w.start()
                            w.wait()
                        return c
                    jax.lax.fori_loop(0, blk, row, 0)
            return carry

        jax.lax.fori_loop(0, nb, loop, 0)

    pl.run_scoped(
        body,
        scratch=pltpu.VMEM((2, blk, batch_ref.shape[1]), batch_ref.dtype),
        fsems=pltpu.SemaphoreType.DMA((2,)),
        wsems=pltpu.SemaphoreType.DMA((2,)))


def ring_write(data: jax.Array, batch: jax.Array, ptr, *,
               capacity: Optional[int] = None, window_start=0,
               block_rows: int = BLOCK_ROWS,
               interpret: Optional[bool] = None) -> jax.Array:
    """Write ``batch`` (n, ...) into ``data`` at ring slots
    ``(ptr + i) % capacity``.

    ``data`` holds global slots ``[window_start, window_start +
    data.shape[0])`` of a ``capacity``-slot pool (defaults: the whole
    pool). Rows landing outside the window are skipped — the shard_map
    path gives every batch group the full batch and lets each keep its
    own rows. Requires n <= capacity (``replay.buffer.write_plan`` drops
    the over-capacity duplicates); rows not written keep their values
    (the output aliases the input buffer)."""
    rows_local, n = data.shape[0], batch.shape[0]
    cap = rows_local if capacity is None else capacity
    if n > cap:
        raise ValueError(f"ring_write of {n} rows into capacity {cap}")
    if n == 0:
        return data
    TRACE_COUNTS["ring_write"] += 1
    orig = data.shape
    d2 = _as2d(data)
    b2 = _as2d(batch.astype(data.dtype))
    # a block must fit the (possibly sharded) destination window: the
    # fast-path DMA statically slices blk rows out of rows_local
    blk = max(1, min(block_rows, n, rows_local))
    pad = (-n) % blk
    if pad:                         # fetches are whole blocks; the tail
        b2 = jnp.pad(b2, ((0, pad), (0, 0)))     # rows are never written
    scal = jnp.stack([jnp.asarray(ptr, jnp.int32),
                      jnp.asarray(window_start, jnp.int32)])
    out = pl.pallas_call(
        functools.partial(_ring_write_kernel, cap=cap, n=n,
                          rows_local=rows_local, blk=blk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(d2.shape, d2.dtype),
        input_output_aliases={2: 0},
        interpret=resolve_interpret(interpret),
    )(scal, b2, d2)
    return out.reshape(orig)


def ring_write_ref(data: jax.Array, batch: jax.Array, ptr, *,
                   capacity: Optional[int] = None,
                   window_start=0) -> jax.Array:
    """jnp oracle for ``ring_write`` (window rows written, rest dropped)."""
    rows_local, n = data.shape[0], batch.shape[0]
    cap = rows_local if capacity is None else capacity
    dest = (jnp.asarray(ptr, jnp.int32) + jnp.arange(n)) % cap
    local = dest - jnp.asarray(window_start, jnp.int32)
    oob = (local < 0) | (local >= rows_local)
    # out-of-window rows redirect to index rows_local -> dropped
    return data.at[jnp.where(oob, rows_local, local)].set(
        batch.astype(data.dtype), mode="drop")


# --------------------------------------------------------------------------- #
# ring gather: blocked batched random row gather
# --------------------------------------------------------------------------- #

def _ring_gather_kernel(info_ref, idx_ref, data_ref, out_ref, sems, *,
                        rows_local: int, blk: int, depth: int):
    """One (blk, F) VMEM out tile per grid step; within the tile the row
    DMAs run ``depth`` deep. Out-of-window rows are zero-filled so the
    shard_map wrapper can sum the partial gathers."""
    b = pl.program_id(0)
    lo = info_ref[0]
    base = b * blk

    def row_copy(i):
        j = idx_ref[base + i] - lo
        inside = (j >= 0) & (j < rows_local)
        jc = jnp.clip(j, 0, rows_local - 1)
        return inside, pltpu.make_async_copy(
            data_ref.at[pl.ds(jc, 1), :],
            out_ref.at[pl.ds(i, 1), :],
            sems.at[jax.lax.rem(i, depth)])

    def start(i):
        inside, cp = row_copy(i)

        @pl.when(inside)
        def _go():
            cp.start()

        @pl.when(~inside)
        def _zero():
            out_ref[pl.ds(i, 1), :] = jnp.zeros(
                (1, out_ref.shape[1]), out_ref.dtype)

    for i in range(min(depth, blk)):    # static warm-up window
        start(i)

    def loop(i, carry):
        inside, cp = row_copy(i)

        @pl.when(inside)
        def _wait():
            cp.wait()

        @pl.when(i + depth < blk)
        def _refill():
            start(i + depth)
        return carry

    jax.lax.fori_loop(0, blk, loop, 0)


def ring_gather(data: jax.Array, idx: jax.Array, *, window_start=0,
                block_rows: int = BLOCK_ROWS,
                interpret: Optional[bool] = None) -> jax.Array:
    """Gather ``pool[idx]`` for a (batch,) int vector of *global* ring
    slots, where ``data`` holds the window ``[window_start, window_start
    + data.shape[0])`` of the pool. Out-of-window rows come back zeroed
    (summed away by the shard_map combiner); with the default window
    every valid slot is inside."""
    TRACE_COUNTS["ring_gather"] += 1
    orig_row = data.shape[1:]
    d2 = _as2d(data)
    rows_local, nfeat = d2.shape
    bsz = idx.shape[0]
    blk = max(1, min(block_rows, bsz))
    pad = (-bsz) % blk
    idx2 = idx.astype(jnp.int32)
    if pad:                          # padded rows index -1 -> zero-filled
        idx2 = jnp.pad(idx2, (0, pad), constant_values=-1)
    nb = idx2.shape[0] // blk
    depth = min(GATHER_DEPTH, blk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((blk, nfeat), lambda b, info, idx: (b, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA((depth,))])
    out = pl.pallas_call(
        functools.partial(_ring_gather_kernel, rows_local=rows_local,
                          blk=blk, depth=depth),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb * blk, nfeat), data.dtype),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(window_start, jnp.int32).reshape(1), idx2, d2)
    return out[:bsz].reshape((bsz,) + orig_row)


def ring_gather_ref(data: jax.Array, idx: jax.Array, *,
                    window_start=0) -> jax.Array:
    """jnp oracle for ``ring_gather`` (zeros for out-of-window rows)."""
    local = idx - jnp.asarray(window_start, jnp.int32)
    inside = (local >= 0) & (local < data.shape[0])
    rows = jnp.take(data, jnp.clip(local, 0, data.shape[0] - 1), axis=0)
    mask = inside.reshape((-1,) + (1,) * (data.ndim - 1))
    return jnp.where(mask, rows, jnp.zeros_like(rows))


# --------------------------------------------------------------------------- #
# PER: Gumbel-top-k sampling scores + priority scatter
# --------------------------------------------------------------------------- #

def per_scores_ref(priorities: jax.Array, gumbel: jax.Array,
                   alpha: float) -> jax.Array:
    """Gumbel-top-k scores over alpha-annealed log-priorities; this is
    BOTH the jnp oracle and the kernel's in-block math, so the two paths
    pick bit-identical samples. Unwritten slots (p == 0) get a true
    ``-inf`` — finite Gumbel noise can never resurrect them (the old
    ``log(max(p, 1e-12)) ~ -16.6`` floor could be out-drawn)."""
    logp = jnp.where(priorities > 0.0,
                     alpha * jnp.log(jnp.maximum(priorities, 1e-12)),
                     -jnp.inf)
    return logp + gumbel


def _per_scores_kernel(pri_ref, g_ref, out_ref, *, alpha: float,
                       keys: bool):
    s = per_scores_ref(pri_ref[...], g_ref[...], alpha)
    out_ref[...] = _order_keys(s) if keys else s


def _lane_dense(x: jax.Array, rows_per_block: int) -> jax.Array:
    """(n,) -> (R, LANES) zero-padded, R a multiple of ``rows_per_block``
    (a reshape of the 1-D tiled layout, so no copy when n divides)."""
    pad = (-x.shape[0]) % (rows_per_block * LANES)
    return (jnp.pad(x, (0, pad)) if pad else x).reshape(-1, LANES)


def _block_rows(n: int, block: int) -> int:
    """Rows of a (rows, LANES) grid block over an n-element vector: the
    whole vector when it fits ``block`` elements, else a multiple of the
    8-row sublane tile."""
    r = pl.cdiv(n, LANES)
    return r if r * LANES <= block else max(8, block // LANES // 8 * 8)


def _score_pass(priorities, gumbel, alpha, block, interpret, *,
                keys=False):
    """The blocked score pass on lane-dense (block_rows, 128) tiles ->
    (R, LANES) float32 scores, or their int32 order keys (``keys``);
    padding slots score -inf."""
    (rows,) = priorities.shape
    br = _block_rows(rows, block)
    p2, g2 = _lane_dense(priorities, br), _lane_dense(gumbel, br)
    spec = pl.BlockSpec((br, LANES), lambda b: (b, 0))
    return pl.pallas_call(
        functools.partial(_per_scores_kernel, alpha=alpha, keys=keys),
        grid=(p2.shape[0] // br,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(
            p2.shape, jnp.int32 if keys else jnp.float32),
        interpret=resolve_interpret(interpret),
    )(p2, g2)


def per_scores(priorities: jax.Array, gumbel: jax.Array, alpha: float, *,
               block: int = 64 * 1024,
               interpret: Optional[bool] = None) -> jax.Array:
    """Blocked elementwise pass over the (rows,) priority vector -> the
    Gumbel-top-k sampling scores (see ``per_scores_ref``), on lane-dense
    (block_rows, 128) tiles. Under shard_map each group scores its local
    priority shard."""
    TRACE_COUNTS["per_scores"] += 1
    out = _score_pass(priorities, gumbel, alpha, block, interpret)
    return out.reshape(-1)[:priorities.shape[0]]


# --------------------------------------------------------------------------- #
# PER: score pass + threshold top-k (group-local index selection)
# --------------------------------------------------------------------------- #

# Index carried by top-k slots that hold no real row (score -inf). Among
# equal -inf scores the selected index is unspecified (callers cycle the
# live draws and never dereference a -inf slot — ``replay.prioritized``),
# so the sentinel only has to stay out of the live index range.
IDX_SENTINEL = 2**31 - 1


def per_topk_ref(priorities: jax.Array, gumbel: jax.Array, alpha: float,
                 k: int, *, window_start=0):
    """jnp oracle for ``per_topk``: dense Gumbel-top-k over the window.

    Returns ``(scores (k,), global_idx (k,), priorities (k,))`` sorted by
    descending score. Indices of -inf entries (fewer than k live rows in
    the window) are real here but a sentinel in the kernel path — compare
    them only where the score is finite."""
    v, i = jax.lax.top_k(per_scores_ref(priorities, gumbel, alpha), k)
    return (v, (i + jnp.asarray(window_start, jnp.int32)).astype(jnp.int32),
            jnp.take(priorities, i))


def merge_topk_candidates(cand_scores: jax.Array, cand_idx: jax.Array,
                          cand_pri: jax.Array, k: int):
    """Reduce ``(groups * k,)`` per-group candidates to the global top-k.

    The candidate vectors MUST be concatenated in the fixed batch-group
    order (``all_gather`` over ``sharding.batch_axes`` — row-major, the
    same order ``batch_group_index`` flattens); with that order and
    ``top_k``'s stable ties the merge returns exactly the dense top-k
    over the whole pool, which is what makes PER draws layout-invariant:
    the global top-k is always a subset of the union of per-group
    top-k's, so no candidate the merge needs can be missing. Each
    candidate's priority rides along for the importance weights."""
    v, pos = jax.lax.top_k(cand_scores, k)
    return v, jnp.take(cand_idx, pos), jnp.take(cand_pri, pos)


# Exact top-k without sorting the window. ``lax.top_k`` with a k this
# large lowers to a sort of every (score, index) pair of the window; the
# selection below reads the window a fixed number of times instead and
# sorts only the k survivors:
#   1. keys: each score's float32 bits as an int32 whose signed order is
#      the total order the TPU's top-k comparator applies to floats
#      (-inf lowest, -0.0 below +0.0; ``_order_keys``);
#   2. the k-th largest key ``t``, bit by bit from the top (one Pallas
#      kernel, the keys resident in VMEM): ``count(key > t) < k <=
#      count(key >= t)``;
#   3. the k rows with key > t, or key == t among the first
#      ``k - count(key > t)`` such rows in index order (top_k's stable
#      tie rule), compacted to their ascending indices;
#   4. ``top_k`` over those k scores orders them as the dense top-k does.

_KTH_CHUNK = 128      # most rows of 128 lanes counted per loop step


def _order_keys(x: jax.Array) -> jax.Array:
    """float32 -> int32 key, monotone in signed order: the non-negative
    floats keep their bits, the negative ones (sign bit set) have the
    other 31 bits flipped. The map is its own inverse on int32 bits."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b < 0, b ^ 0x7FFFFFFF, b)


def _key_scores(keys: jax.Array) -> jax.Array:
    """Inverse of ``_order_keys``: the float32 scores, bit for bit."""
    return jax.lax.bitcast_convert_type(_order_keys(keys), jnp.float32)


def _kth_key_kernel(key_ref, out_ref, *, k: int, chunk: int):
    """Bitwise search for the k-th largest key over the VMEM-resident
    (R, LANES) keys: 32 counting passes, one per bit from the top, then
    one more for ``count(key > t)``. Writes t to row 0 of the output
    and that count to row 1."""
    nc = key_ref.shape[0] // chunk

    def count(pred):
        def step(c, acc):
            x = key_ref[pl.ds(pl.multiple_of(c * chunk, chunk), chunk), :]
            return acc + jnp.where(pred(x), 1, 0)
        acc = jax.lax.fori_loop(0, nc, step,
                                jnp.zeros((chunk, LANES), jnp.int32))
        return jnp.sum(jnp.sum(acc, axis=0, keepdims=True), axis=1,
                       keepdims=True)

    def bit(b, t):
        # t is the unsigned prefix found so far, moved to signed order by
        # its sign bit; flipping bit 31 - b sets that bit of the prefix
        cand = t ^ jax.lax.shift_left(jnp.int32(1), 31 - b)
        return jnp.where(count(lambda x: x >= cand) >= k, cand, t)

    t = jax.lax.fori_loop(0, 32, bit,
                          jnp.full((1, 1), -2**31, jnp.int32))
    n_gt = count(lambda x: x > t)
    row = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    out_ref[...] = jnp.where(row == 0, t, n_gt)


def _kth_key(keys: jax.Array, k: int, interpret):
    """-> (t, count(keys > t)) for the k-th largest of the (R, LANES)
    keys, as int32 scalars."""
    chunk = math.gcd(keys.shape[0], _KTH_CHUNK)
    out = pl.pallas_call(
        functools.partial(_kth_key_kernel, k=k, chunk=chunk),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_shape=jax.ShapeDtypeStruct((8, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=keys.size * 4 + (8 << 20)),
        interpret=resolve_interpret(interpret),
    )(keys)
    return out[0, 0], out[1, 0]


def _lane_prefix(mask: jax.Array) -> jax.Array:
    """Inclusive count of the set lanes up to each lane of a (R, LANES)
    bool, as a matmul with a triangle of ones: 0/1 operands are exact at
    any matmul precision, and the sums (<= 128) in float32."""
    tri = (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1))
    return jnp.dot(mask.astype(jnp.float32),
                   tri.astype(jnp.float32)).astype(jnp.int32)


def _top_rows(keys: jax.Array, t, n_gt, k: int) -> jax.Array:
    """The k selected rows of the (R, LANES) keys -> their flat indices
    (k,), ascending: every key > t, then the first ``k - n_gt`` keys
    equal to t in index order. The compaction is two-level: a slot's
    128-lane row comes from the rows' running counts, its lane from the
    row's own running count."""
    eq = keys == t
    eq_row = jnp.sum(eq, axis=1, dtype=jnp.int32)
    eq_before = jnp.cumsum(eq_row) - eq_row
    sel = (keys > t) | (eq & (eq_before[:, None] + _lane_prefix(eq)
                              <= k - n_gt))
    count = jnp.sum(sel, axis=1, dtype=jnp.int32)
    start = jnp.cumsum(count) - count          # first slot of each row
    row = jax.lax.cummax(
        jnp.zeros((k,), jnp.int32).at[jnp.where(count > 0, start, k)].max(
            jnp.arange(keys.shape[0], dtype=jnp.int32), mode="drop"))
    rank = jnp.arange(k, dtype=jnp.int32) - jnp.take(start, row)
    lane = jnp.sum(jnp.take(_lane_prefix(sel), row, axis=0)
                   <= rank[:, None], axis=1, dtype=jnp.int32)
    return row * LANES + lane


def per_topk(priorities: jax.Array, gumbel: jax.Array, alpha: float,
             k: int, *, window_start=0, block: int = 64 * 1024,
             interpret: Optional[bool] = None):
    """PER selection over the (rows,) priority window: the score pass
    (``per_scores``' math, writing order keys), then the exact top-k by
    threshold — a Pallas kernel finds the k-th largest key, XLA
    compacts the k rows at or above it and sorts only those (the steps
    above ``_order_keys``).

    Returns ``(scores (k,), global_idx (k,), priorities (k,))`` — the
    window's k best live candidates, indices offset by ``window_start``
    so each mesh group emits globally-addressed candidates for the
    cross-group merge (``merge_topk_candidates``). Matches
    ``per_topk_ref`` exactly on every finite-score slot; -inf slots
    carry ``IDX_SENTINEL`` (their index is unspecified and unused —
    draws past the live-row count cycle the live draws)."""
    (rows,) = priorities.shape
    if k > rows:
        raise ValueError(f"per_topk of k={k} from a {rows}-row window")
    TRACE_COUNTS["per_topk"] += 1
    TRACE_COUNTS["per_topk_threshold"] += 1
    keys = _score_pass(priorities, gumbel, alpha, block, interpret,
                       keys=True)
    t, n_gt = _kth_key(keys, k, interpret)
    idx = _top_rows(keys, t, n_gt, k)
    v, pos = jax.lax.top_k(_key_scores(jnp.take(keys.reshape(-1), idx)), k)
    i = jnp.take(idx, pos)
    gidx = i + jnp.asarray(window_start, jnp.int32)
    return (v, jnp.where(v > -jnp.inf, gidx, IDX_SENTINEL),
            jnp.take(priorities, i))


def _priority_scatter_kernel(lo_ref, idx_ref, val_ref, pri_ref, out_ref,
                             buf, sem, *, k: int, rows_local: int, br: int):
    """Blocked read-modify-write of the lane-dense (R, LANES) priority
    vector, which stays in HBM: each (br, LANES) block is DMA'd into
    VMEM, every in-window update landing in it is applied as a masked
    lane store on its row, and the block is DMA'd back. Updates apply
    in index order, so a repeated slot keeps its last value."""
    del pri_ref                     # aliased with out_ref
    lo = lo_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def block(b, carry):
        rows = pl.ds(b * br, br)
        cp = pltpu.make_async_copy(out_ref.at[rows, :], buf, sem.at[0])
        cp.start()
        cp.wait()

        def one(i, c):
            slot = idx_ref[i] - lo
            d = slot - b * (br * LANES)

            @pl.when((slot < rows_local) & (d >= 0) & (d < br * LANES))
            def _write():
                r = pl.ds(d // LANES, 1)
                buf[r, :] = jnp.where(lane == d % LANES, val_ref[i],
                                      buf[r, :])
            return c

        jax.lax.fori_loop(0, k, one, 0)
        cp = pltpu.make_async_copy(buf, out_ref.at[rows, :], sem.at[0])
        cp.start()
        cp.wait()
        return carry

    jax.lax.fori_loop(0, out_ref.shape[0] // br, block, 0)


def priority_scatter(priorities: jax.Array, idx: jax.Array,
                     values: jax.Array, *, window_start=0,
                     block: int = 1024 * 1024,
                     interpret: Optional[bool] = None) -> jax.Array:
    """``priorities[idx - window_start] = values`` for the in-window
    subset of the (arbitrary, PER-sampled) indices; out-of-window
    updates are dropped (they belong to another group's shard). The
    vector stays in HBM and is updated in place via aliasing when the
    caller donates it (and its length is a multiple of the block).

    Every block loops over all k updates, so the default block (4 MiB
    of VMEM) holds a whole 2^20-row window: one pass over the updates."""
    TRACE_COUNTS["priority_scatter"] += 1
    (rows_local,) = priorities.shape
    k = idx.shape[0]
    br = _block_rows(rows_local, block)
    p2 = _lane_dense(priorities, br)
    out = pl.pallas_call(
        functools.partial(_priority_scatter_kernel, k=k,
                          rows_local=rows_local, br=br),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(p2.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((br, LANES), jnp.float32),
                        pltpu.SemaphoreType.DMA((1,))],
        input_output_aliases={3: 0},
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(window_start, jnp.int32).reshape(1),
      idx.astype(jnp.int32), values.astype(jnp.float32), p2)
    return out.reshape(-1)[:rows_local]


def priority_scatter_ref(priorities: jax.Array, idx: jax.Array,
                         values: jax.Array, *, window_start=0) -> jax.Array:
    """jnp oracle for ``priority_scatter``."""
    rows_local = priorities.shape[0]
    local = idx - jnp.asarray(window_start, jnp.int32)
    oob = (local < 0) | (local >= rows_local)
    return priorities.at[jnp.where(oob, rows_local, local)].set(
        values.astype(priorities.dtype), mode="drop")


# --------------------------------------------------------------------------- #
# PR-1 row-at-a-time kernels: kept as the bench baseline
# --------------------------------------------------------------------------- #

def _ring_write_rowloop_kernel(ptr_ref, batch_ref, data_ref, out_ref,
                               *, cap: int, n: int):
    del data_ref                    # aliased with out_ref
    ptr = ptr_ref[0]

    def body(i, carry):
        idx = jax.lax.rem(ptr + i, cap)
        out_ref[pl.ds(idx, 1), :] = batch_ref[pl.ds(i, 1), :]
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def ring_write_rowloop(data: jax.Array, batch: jax.Array, ptr, *,
                       interpret: Optional[bool] = None) -> jax.Array:
    """The PR-1 per-row dynamic-slice ring write (whole pool in VMEM) —
    the baseline ``benchmarks/bench_replay_kernels.py`` regresses the
    blocked kernel against."""
    cap, n = data.shape[0], batch.shape[0]
    if n > cap:
        raise ValueError(f"ring_write of {n} rows into capacity {cap}")
    orig = data.shape
    d2 = _as2d(data)
    b2 = _as2d(batch.astype(data.dtype))
    out = pl.pallas_call(
        functools.partial(_ring_write_rowloop_kernel, cap=cap, n=n),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(d2.shape, d2.dtype),
        input_output_aliases={2: 0},
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(ptr, jnp.int32).reshape((1,)), b2, d2)
    return out.reshape(orig)


def _ring_gather_rowloop_kernel(idx_ref, data_ref, out_ref, *, bsz: int):
    def body(i, carry):
        j = idx_ref[i]
        out_ref[pl.ds(i, 1), :] = data_ref[pl.ds(j, 1), :]
        return carry

    jax.lax.fori_loop(0, bsz, body, 0)


def ring_gather_rowloop(data: jax.Array, idx: jax.Array, *,
                        interpret: Optional[bool] = None) -> jax.Array:
    """The PR-1 per-row gather (whole pool in VMEM) — bench baseline."""
    orig_row = data.shape[1:]
    d2 = _as2d(data)
    bsz = idx.shape[0]
    out = pl.pallas_call(
        functools.partial(_ring_gather_rowloop_kernel, bsz=bsz),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bsz, d2.shape[1]), data.dtype),
        interpret=resolve_interpret(interpret),
    )(idx.astype(jnp.int32), d2)
    return out.reshape((bsz,) + orig_row)
