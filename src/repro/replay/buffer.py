"""Device-resident sharded replay ring buffer — the paper's shared memory.

The paper keeps the replay pool in shared RAM so samplers write and the
updater reads without either blocking (§3.3.2). The TPU-native analogue is
a **donated pytree living in HBM**: ``add`` is a jitted scatter into the
ring (in-place thanks to buffer donation) and ``sample`` a jitted gather,
so experience never leaves the accelerator and neither side "dumps" data.

Row format: every transition is ONE packed float32 record of
``RECORD_LANES`` lanes (the fields side by side, zero-padded), so the
ring is a single ``(capacity, RECORD_LANES)`` array. That is the shape
the TPU's tiled HBM layout and a Pallas row DMA both want: Mosaic only
moves lane-aligned slices, and a separate ``(capacity, 3)`` leaf would
be relaid out into a 128-lane temporary around every kernel call
(``tests/test_tpu_compile.py`` checks the ring's bytes with
``memory_analysis()``). A write is one scatter and a sample one gather
of whole records; ``ReplayState.data`` unpacks the named fields.

Batch sharding: rows are laid out over the ``batch`` logical axis, so on a
mesh each data-parallel group owns a slice of the pool — the multi-pod
generalization of one shared-RAM pool per desktop.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.hlolint.contract import EntrypointContract
from repro.distributed.sharding import current_rules, shard
from repro.kernels import ops as kops

# hlolint contract for the donated ring write: the in-place HBM
# scatter IS the paper's shared-memory pool — if donation stops
# aliasing, every add copies the whole (capacity, ...) ring
HLOLINT_CONTRACTS = (
    EntrypointContract(name="replay_add_batch", module=__name__,
                       donates=True),
)


def _ring_mode(cap_rows: int, sample_rows=None) -> str:
    """Which form a ring op traces to: ``"pallas"`` (single-device
    blocked kernel), ``"shard"`` (the kernel inside ``shard_map`` over
    the active mesh's batch axes — each group operates on its local ring
    shard), or ``"jnp"`` (kernels off, or the active rules can't tile
    the op: no batch axis, or the row counts don't divide the group
    count). ``sample_rows`` is the gather's output row count, which the
    shard path's ``psum_scatter`` must also split evenly."""
    if not kops.pallas_enabled():
        return "jnp"
    r = current_rules()
    if not r.active:
        return "pallas"
    if not r.batch:
        return "jnp"
    groups = r.axis_size(r.batch)
    if cap_rows % groups or (sample_rows is not None
                             and sample_rows % groups):
        return "jnp"
    return "shard"


def _per_select_mode(cap_rows: int, k: int) -> str:
    """Dispatch for the PER two-phase top-k selection: like
    ``_ring_mode``, but the ``"shard"`` path additionally needs every
    batch group's ring shard to hold at least ``k`` rows — each group
    emits ``k`` candidates, and fewer rows than candidates would drop
    live rows from the merge (the global top-k is only guaranteed to be
    covered when every group can surface its full k)."""
    mode = _ring_mode(cap_rows)
    if mode != "shard":
        return mode
    r = current_rules()
    if k > cap_rows // r.axis_size(r.batch):
        return "jnp"
    return mode


#: lanes of one packed ring record: one float32 vreg row, the unit a
#: ring kernel moves with a single DMA
RECORD_LANES = 128

#: static record layout: ((field name, row shape), ...) in lane order
Layout = Tuple[Tuple[str, Tuple[int, ...]], ...]


def record_width(layout: Layout) -> int:
    used = sum(math.prod(shape) for _, shape in layout)
    return RECORD_LANES * max(1, -(-used // RECORD_LANES))


def pack_rows(layout: Layout, fields: Dict[str, jax.Array]) -> jax.Array:
    """{name: (n, *shape)} -> (n, record_width) float32 records."""
    n = fields[layout[0][0]].shape[0]
    cols = [fields[name].reshape(n, -1).astype(jnp.float32)
            for name, _ in layout]
    used = sum(c.shape[1] for c in cols)
    cols.append(jnp.zeros((n, record_width(layout) - used), jnp.float32))
    return jnp.concatenate(cols, axis=1)


def unpack_rows(layout: Layout, rows: jax.Array) -> Dict[str, jax.Array]:
    """(n, record_width) records -> {name: (n, *shape)}."""
    out, off = {}, 0
    for name, shape in layout:
        w = math.prod(shape)
        out[name] = rows[:, off:off + w].reshape((rows.shape[0],) + shape)
        off += w
    return out


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("rows", "ptr", "size"),
                   meta_fields=("layout",))
@dataclasses.dataclass(frozen=True)
class ReplayState:
    rows: jax.Array                # (capacity, record_width) f32 records
    ptr: jax.Array                 # int32 next write slot
    size: jax.Array                # int32 filled rows
    layout: Layout                 # static: field order and row shapes

    @property
    def data(self) -> Dict[str, jax.Array]:
        """The ring's named fields, each (capacity, *row_shape)."""
        return unpack_rows(self.layout, self.rows)


def init_replay(capacity: int, specs: Dict[str, Tuple[Tuple[int, ...],
                                                      jnp.dtype]]
                ) -> ReplayState:
    """specs: name -> (row_shape, dtype). E.g. {"obs": ((3,), f32), ...}.
    Every field is float32: the fields share one packed record."""
    for k, (_, d) in specs.items():
        if jnp.dtype(d) != jnp.float32:
            raise ValueError(f"replay field {k!r} is {jnp.dtype(d)}; "
                             f"ring records are float32")
    layout = tuple((k, tuple(s)) for k, (s, _) in specs.items())
    return ReplayState(
        rows=jnp.zeros((capacity, record_width(layout)), jnp.float32),
        ptr=jnp.zeros((), jnp.int32), size=jnp.zeros((), jnp.int32),
        layout=layout)


def specs_for_env(obs_dim: int, act_dim: int):
    f32 = jnp.float32
    return {"obs": ((obs_dim,), f32), "act": ((act_dim,), f32),
            "rew": ((), f32), "next_obs": ((obs_dim,), f32),
            "done": ((), f32)}


def trainer_specs(obs_dim: int, act_dim: int):
    """The field set the trainer actually writes: env fields plus the
    ``"disc"`` row (gamma^k(1-done), added by the n-step transform).
    Single source of truth for the pipeline AND the adaptation probe —
    if they drift, ``auto_tune`` times the wrong update HLO."""
    specs = dict(specs_for_env(obs_dim, act_dim))
    specs["disc"] = ((), jnp.float32)
    return specs


def write_plan(ptr, n: int, cap: int):
    """Ring slots for an n-row write: (ptr0, keep) — slot of the first
    surviving row and how many of the *newest* rows survive. Writes
    larger than the capacity keep only the newest ``capacity`` rows (the
    older ones would have been overwritten within the same call, and
    duplicate ring indices make ``.at[idx].set`` winner-undefined), so
    the result matches writing the rows one at a time. Shared with the
    prioritized pool so priorities land on exactly the data's slots."""
    drop = max(0, n - cap)              # static: shapes are trace constants
    return (ptr + drop) % cap, n - drop


def ring_slots_mask(cap: int, ptr0, n: int) -> jax.Array:
    """(cap,) bool: the slots an n-row write starting at ``ptr0`` lands
    on — an elementwise mask, so a per-slot vector next to the ring (the
    PER priorities) is written without a scatter."""
    return (jnp.arange(cap, dtype=jnp.int32) - ptr0) % cap < n


def scatter_rows(dest: jax.Array, rows: jax.Array, ptr0) -> jax.Array:
    """dest[(ptr0 + i) % cap] = rows via the blocked Pallas ring kernel
    (shard_map'd onto the mesh under active rules) or the jnp scatter,
    per ``_ring_mode`` (read at trace time)."""
    mode = _ring_mode(dest.shape[0])
    if mode == "pallas":
        return kops.ring_write(dest, rows, ptr0)
    if mode == "shard":
        return kops.ring_write_sharded(dest, rows, ptr0, current_rules())
    idx = (ptr0 + jnp.arange(rows.shape[0])) % dest.shape[0]
    return dest.at[idx].set(rows.astype(dest.dtype))


def gather_rows(data: jax.Array, idx: jax.Array) -> jax.Array:
    """data[idx] via the blocked Pallas ring kernel (shard_map'd onto
    the mesh under active rules) or jnp.take, per ``_ring_mode`` (read
    at trace time)."""
    mode = _ring_mode(data.shape[0], idx.shape[0])
    if mode == "pallas":
        return kops.ring_gather(data, idx)
    if mode == "shard":
        return kops.ring_gather_sharded(data, idx, current_rules())
    return jnp.take(data, idx, axis=0)


def add_batch(state: ReplayState, batch: Dict[str, jax.Array]) -> ReplayState:
    """Scatter N new rows at (ptr + i) % capacity. Jit with donated state —
    the write happens in place in HBM (shared-memory semantics). See
    ``write_plan`` for oversized-write handling."""
    new = pack_rows(state.layout, batch)
    n = new.shape[0]
    cap = state.rows.shape[0]
    ptr0, keep = write_plan(state.ptr, n, cap)
    # pin the ring to the batch axis so GSPMD never un-shards the pool
    # across a megastep's scan carries (no-op without active rules)
    rows = shard(scatter_rows(state.rows, new[n - keep:], ptr0),
                 "batch", None)
    return dataclasses.replace(state, rows=rows,
                               ptr=(state.ptr + n) % cap,
                               size=jnp.minimum(state.size + n, cap))


def sample(state: ReplayState, key, batch_size: int) -> Dict[str, jax.Array]:
    """Uniform random gather of ``batch_size`` rows (with replacement —
    the paper's large-batch regime has batch >> new-experience rate)."""
    cap = state.rows.shape[0]
    idx = jax.random.randint(key, (batch_size,), 0,
                             jnp.maximum(state.size, 1))
    # ring alignment: the oldest live row sits at ptr when full
    idx = (idx + jnp.where(state.size >= cap, state.ptr, 0)) % cap
    return unpack_rows(state.layout, gather_rows(state.rows, idx))


def _pallas_keyed_jit(fn):
    """Donated-jit factory keyed on the trace-time context (use_pallas
    switch + active mesh rules — see ``_ring_trace_key``): both steer
    what gets baked into the trace (kernel choice, sharding
    constraints), so a shared jit cache would otherwise pin whichever
    context was traced first for a given shape. Each entry wraps a
    FRESH function object: jax's lowering cache keys on function
    identity + avals and cannot see our contextvars, so distinct jit
    wrappers around the same ``fn`` would still share one trace."""
    return functools.lru_cache(maxsize=None)(
        # hlolint: entrypoint[replay_add_batch]
        lambda key: functools.partial(jax.jit, donate_argnums=(0,))(
            functools.wraps(fn)(lambda *a, **kw: fn(*a, **kw))))


def _ring_trace_key():
    """Everything ``add_batch`` reads from context at trace time: the
    Pallas switch (``_ring_mode`` derives from it + the rules + shapes,
    and shapes already key the jit cache) and the mesh rules (whose
    ``shard`` constraints would otherwise leak across trainers — e.g.
    commit a meshless trainer's replay onto another trainer's mesh)."""
    return (kops.pallas_enabled(), current_rules())


_add_batch_jit = _pallas_keyed_jit(add_batch)


def add_batch_jit(state: ReplayState, batch) -> ReplayState:
    return _add_batch_jit(_ring_trace_key())(state, batch)


def sample_jit(batch_size: int):
    return jax.jit(functools.partial(sample, batch_size=batch_size))
