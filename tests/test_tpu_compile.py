"""AOT compiles for a TPU v5e chip that is described, not attached.

Mosaic refuses what interpret mode runs happily (lane-unaligned DMA
slices, VMEM overflows, primitives without a TPU lowering), so the
replay kernels of the training main path are compiled here for a real
chip at deployment widths: capacity 262,144, batch 8192, the trainer's
packed ring record for ``pendulum`` and ``hopper``. Nothing runs; these
tests say the kernels compile and fit, not how fast they are.

The topology is described inside a fixture (never at import time: only
one process may load the TPU library), and the persistent compilation
cache is off around the compiles — an entry compiled for a described
chip cannot be read back on the CPU.
"""
import contextlib
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from _hlo_program import program
from jax.sharding import SingleDeviceSharding

from repro.kernels import _compat
from repro.kernels import replay_ops as rops
from repro.replay import buffer as rb

CAP, BATCH, WRITE_ROWS = 262_144, 8192, 1024 * 32
ENV_DIMS = {"pendulum": (3, 1), "hopper": (8, 2)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _width(env):
    return rb.record_width(rb.init_replay(1, rb.trainer_specs(
        *ENV_DIMS[env])).layout)


def _compile(fn, *args, donate=()):
    c = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    assert "tpu_custom_call" in c.as_text()
    return c.memory_analysis()


@pytest.mark.parametrize("env", sorted(ENV_DIMS))
def test_ring_write_compiles_in_place(one_chip, env):
    w = _width(env)
    ma = _compile(lambda d, b, p: rops.ring_write(d, b, p, interpret=False),
                  _spec(one_chip, (CAP, w)),
                  _spec(one_chip, (WRITE_ROWS, w)),
                  _spec(one_chip, (), jnp.int32), donate=(0,))
    ring = CAP * w * 4
    # the ring is its own bytes (no lane padding beyond the record), is
    # written in place, and needs no relayout temporary
    assert ma.alias_size_in_bytes == ring
    scalar = ma.argument_size_in_bytes - ring - WRITE_ROWS * w * 4
    assert 0 < scalar <= 512          # the ptr, in one padded allocation
    assert ma.temp_size_in_bytes == 0


@pytest.mark.parametrize("env", sorted(ENV_DIMS))
def test_ring_gather_compiles(one_chip, env):
    w = _width(env)
    ma = _compile(lambda d, i: rops.ring_gather(d, i, interpret=False),
                  _spec(one_chip, (CAP, w)),
                  _spec(one_chip, (BATCH,), jnp.int32))
    assert ma.output_size_in_bytes == BATCH * w * 4
    assert ma.temp_size_in_bytes == 0


def test_per_topk_compiles(one_chip):
    """The threshold selection sorts only the k survivors: no ``sort``
    in the program has an operand of CAP elements, and its temporaries
    stay within a few CAP-sized 32-bit vectors."""
    c = jax.jit(lambda p, g: rops.per_topk(p, g, 0.6, BATCH,
                                           interpret=False)).lower(
        _spec(one_chip, (CAP,)), _spec(one_chip, (CAP,))).compile()
    text = c.as_text()
    assert "tpu_custom_call" in text
    sorts = [line.split(" sort(")[0] for line in text.splitlines()
             if " sort(" in line]
    assert sorts
    for shape in sorts:
        for dims in re.findall(r"\[([\d,]+)\]", shape):
            assert math.prod(map(int, dims.split(","))) < CAP, shape
    assert c.memory_analysis().temp_size_in_bytes <= 4 * CAP * 4


@pytest.mark.parametrize("window_start", [0, CAP])
def test_priority_scatter_compiles_in_place(one_chip, window_start):
    ma = _compile(lambda p, i, v: rops.priority_scatter(
        p, i, v, window_start=window_start, interpret=False),
        _spec(one_chip, (CAP,)), _spec(one_chip, (BATCH,), jnp.int32),
        _spec(one_chip, (BATCH,)), donate=(0,))
    # the priority vector stays in HBM, updated in place
    assert ma.alias_size_in_bytes == CAP * 4
    assert ma.temp_size_in_bytes == 0


def test_megastep_compiles_with_mosaic_kernels(one_chip, monkeypatch):
    """The single-device fused megastep with the kernels on: every ring
    op must lower to Mosaic, not the interpreter the CPU backend would
    pick while tracing."""
    from repro.core import SpreezeConfig, SpreezeTrainer
    monkeypatch.setattr(_compat, "interpret_default", lambda: False)
    jax.clear_caches()            # no interpret-mode trace may be reused
    tr = SpreezeTrainer(SpreezeConfig(
        env_name="pendulum", num_envs=1024, chunk_len=32,
        batch_size=BATCH, replay_capacity=CAP, updates_per_round=8,
        rounds_per_dispatch=4, use_pallas=True))
    args = jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype),
                        (tr.state, tr.replay, tr.env_states, tr.key))
    c = tr._megastep.lower(*args).compile()
    jax.clear_caches()
    assert c.as_text().count("tpu_custom_call") >= 2  # ring write + gather
    assert (c.memory_analysis().alias_size_in_bytes
            >= tr.replay.rows.size * 4)                # ring donated


@pytest.mark.parametrize("prioritized", [False, True])
def test_megastep_stage_scopes_leave_the_v5e_program_unchanged(
        one_chip, monkeypatch, prioritized):
    """The megastep's stage scopes (``pipeline._scope``) name its ops in
    the metadata a device trace reads, and change no op of the program
    the chip's compiler makes."""
    from repro.core import SpreezeConfig, SpreezeTrainer, pipeline
    monkeypatch.setattr(_compat, "interpret_default", lambda: False)

    def optimized():
        jax.clear_caches()
        tr = SpreezeTrainer(SpreezeConfig(
            env_name="hopper" if prioritized else "pendulum",
            num_envs=64, chunk_len=8, batch_size=512, replay_capacity=8192,
            updates_per_round=2, rounds_per_dispatch=2,
            prioritized=prioritized, nstep=3 if prioritized else 1,
            use_pallas=True))
        args = jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype),
                            (tr.state, tr.replay, tr.env_states, tr.key))
        return tr._megastep.lower(*args).compile().as_text()

    scoped = optimized()
    monkeypatch.setattr(pipeline, "_scope",
                        lambda name: contextlib.nullcontext())
    plain = optimized()
    jax.clear_caches()
    assert "/update/" in scoped and "/update/" not in plain
    ops = program(scoped)
    assert "tpu_custom_call" in ops and ", metadata=" not in ops
    assert ops == program(plain)
