"""Production meshes for the dry-run (TPU v5e pods; host-CPU placeholders).

Every mesh here is built with ``AxisType.Auto`` axes: the trainer and
the model stack are written for GSPMD propagation (sharding constraints
plus ``shard_map`` islands), and ``jax.make_mesh`` otherwise builds
``Explicit`` axes, under which a scatter into a batch-sharded ring
raises ``ShardingTypeError``.

A FUNCTION, not a module constant, so importing this never touches jax
device state — the dry-run entry point sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then calls ``make_production_mesh``.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType, Mesh


def _auto(n: int):
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False,
                         data: int = 16, model: int = 16) -> Mesh:
    """16x16 = 256 chips/pod ("data","model"); multi-pod adds the ``pod``
    axis: (2,16,16) = 512 chips. The ``pod`` axis doubles as the Spreeze
    actor/critic axis under ``spreeze_rules`` (DESIGN.md §2).

    ``data``/``model`` reshape the intra-pod axes (data*model must stay
    256) — the §Perf iterations use e.g. 32x8 for expert parallelism."""
    assert data * model == 256, (data, model)
    shape = (2, data, model) if multi_pod else (data, model)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 512 if multi_pod else 256
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"need {need} devices for the production mesh, found "
            f"{len(devs)}; run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(launch/dryrun.py sets this automatically)")
    return jax.make_mesh(shape, axes, devices=devs[:need],
                         axis_types=_auto(len(axes)))


def make_ac_mesh(ac: int = 2, batch: int = 0) -> Mesh:
    """(ac, batch) mesh for the sharded trainer megastep: the ``ac`` axis
    carries the double-Q ensemble (paper Fig. 2b dual-GPU split), the
    ``batch`` axis the replay rows. ``batch=0`` takes every remaining
    device. Host-CPU testing: force devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``."""
    devs = jax.devices()
    if batch <= 0:
        batch = max(1, len(devs) // ac)
    need = ac * batch
    if len(devs) < need:
        raise RuntimeError(
            f"need {need} devices for an {ac}x{batch} ac mesh, found "
            f"{len(devs)}; run under XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need}")
    return jax.make_mesh((ac, batch), ("ac", "batch"), devices=devs[:need],
                         axis_types=_auto(2))


def parse_ac_mesh(spec: str) -> Mesh:
    """CLI 'ACxBATCH' spec (e.g. '2x4') -> the ac mesh. Shared by the
    example driver and the table2/table3 benchmarks."""
    try:
        ac, batch = (int(v) for v in spec.lower().split("x"))
    except ValueError:
        raise ValueError(
            f"bad mesh spec {spec!r}: want 'ACxBATCH', e.g. '2x4'") \
            from None
    return make_ac_mesh(ac, batch)


def ring_shard_groups(mesh: Mesh, placement: str = "ac") -> int:
    """Number of replay-ring shards (batch groups) an ('ac','batch')
    trainer mesh induces under the given placement — the divisor
    ``replay_capacity`` and ``batch_size`` must both honor for the
    shard_map ring kernels to run mesh-native instead of falling back
    to the jnp scatter/gather (``SpreezeTrainer._check_mesh`` validates
    both through here)."""
    from repro.distributed.sharding import trainer_rules
    rules = trainer_rules(mesh, placement)
    return rules.axis_size(rules.batch)


def make_debug_mesh(data: int = 1, model: int = 1) -> Optional[Mesh]:
    """Small mesh over however many devices exist (tests)."""
    n = data * model
    devs = jax.devices()
    if len(devs) < n:
        return None
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=devs[:n], axis_types=_auto(2))
