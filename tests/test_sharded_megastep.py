"""Multi-device megastep: sharding specs, config validation, and the
single-vs-sharded equivalence check under a forced 8-device host mesh.

The equivalence check needs the process to have been born with 8 XLA
host devices; when this suite runs with fewer (the default tier-1 run),
it is delegated to a subprocess that sets XLA_FLAGS itself. The sharded
CI job runs the whole suite under the flag, exercising the in-process
path.
"""
import os
import subprocess
import sys

import jax
import pytest

from repro.core import SpreezeConfig, SpreezeTrainer
from repro.launch.mesh import make_ac_mesh, make_debug_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECK = os.path.join(ROOT, "tests", "sharded_check.py")


def _cfg(**kw):
    base = dict(env_name="pendulum", algo="sac", num_envs=2, batch_size=32,
                chunk_len=4, updates_per_round=2, warmup_frames=32,
                replay_capacity=256, eval_every_rounds=10**9, seed=3)
    base.update(kw)
    return SpreezeConfig(**base)


@pytest.mark.slow
def test_sharded_matches_single_device_megastep():
    if len(jax.devices()) >= 8:
        sys.path.insert(0, os.path.dirname(CHECK))
        try:
            from sharded_check import run_check
        finally:
            sys.path.pop(0)
        assert run_check()
        return
    pypath = os.pathsep.join(
        p for p in (os.path.join(ROOT, "src"),
                    os.environ.get("PYTHONPATH", "")) if p)
    # preserve inherited tuning flags; only force the device count
    xla = [f for f in os.environ.get("XLA_FLAGS", "").split()
           if "xla_force_host_platform_device_count" not in f]
    xla.append("--xla_force_host_platform_device_count=8")
    env = dict(os.environ, PYTHONPATH=pypath, XLA_FLAGS=" ".join(xla))
    r = subprocess.run([sys.executable, CHECK], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "sharded-megastep-equivalence: OK" in r.stdout


def test_trivial_ac_mesh_runs_sharded_path():
    """A (1, 1) ac x batch mesh exercises the whole sharded codepath
    (in/out shardings, use_rules tracing, device_put placement) on any
    device count — math must match the meshless trainer exactly."""
    import numpy as np
    mesh = make_ac_mesh(1, 1)
    tr_m = SpreezeTrainer(_cfg(mesh=mesh, rounds_per_dispatch=2))
    tr_r = SpreezeTrainer(_cfg(rounds_per_dispatch=2))
    for tr in (tr_m, tr_r):
        tr._warmup()
        (tr.state, tr.replay, tr.env_states, tr.key,
         tr.last_metrics) = tr._megastep(tr.state, tr.replay,
                                         tr.env_states, tr.key)
    assert int(tr_m.replay.ptr) == int(tr_r.replay.ptr)
    np.testing.assert_array_equal(np.asarray(tr_m.key),
                                  np.asarray(tr_r.key))
    for a, b in zip(jax.tree.leaves(tr_m.state.actor),
                    jax.tree.leaves(tr_r.state.actor)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_mesh_rejects_indivisible_q_ensemble():
    """ddpg's single Q tower cannot shard over an ac axis of size 2 —
    must fail with a clear ValueError, not a low-level XLA partition
    error (the check reads the REAL ensemble size from the state)."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices for an ac axis of size 2")
    mesh = make_ac_mesh(2, 1)
    with pytest.raises(ValueError, match="ensemble"):
        SpreezeTrainer(_cfg(mesh=mesh, algo="ddpg"))


def test_mesh_with_ambient_pallas_switch_runs_shard_map_ring():
    """use_pallas + mesh: the trainer inherits the ambient switch at
    construction (cfg.use_pallas=None) and pins it into the megastep
    trace — which now runs the shard_map ring kernels on each group's
    local ring shard instead of the old silent jnp fallback."""
    import numpy as np
    from repro.kernels import ops as kops
    from repro.kernels import replay_ops as rops
    mesh = make_ac_mesh(1, 1)
    rops.reset_trace_counts()
    with kops.use_pallas(True):
        tr = SpreezeTrainer(_cfg(mesh=mesh, rounds_per_dispatch=2))
        tr._warmup()
        (tr.state, tr.replay, tr.env_states, tr.key,
         tr.last_metrics) = tr._megastep(tr.state, tr.replay,
                                         tr.env_states, tr.key)
    assert tr.use_pallas
    assert rops.TRACE_COUNTS["shard:ring_write"] > 0, rops.TRACE_COUNTS
    assert rops.TRACE_COUNTS["shard:ring_gather"] > 0, rops.TRACE_COUNTS
    assert np.isfinite(np.asarray(tr.last_metrics["critic_loss"])).all()
    assert int(tr.replay.size) > 0


def test_trainer_pins_pallas_switch_against_ambient_drift():
    """cfg.use_pallas=False must hold even when the caller flips the
    ambient switch on before the first (lazy) megastep trace."""
    from repro.kernels import ops as kops
    from repro.kernels import replay_ops as rops
    mesh = make_ac_mesh(1, 1)
    tr = SpreezeTrainer(_cfg(mesh=mesh, use_pallas=False))
    rops.reset_trace_counts()
    with kops.use_pallas(True):     # ambient on; trainer pinned off
        tr._warmup()
        (tr.state, tr.replay, tr.env_states, tr.key,
         tr.last_metrics) = tr._megastep(tr.state, tr.replay,
                                         tr.env_states, tr.key)
    assert rops.TRACE_COUNTS["shard:ring_write"] == 0, rops.TRACE_COUNTS
    assert rops.TRACE_COUNTS["ring_write"] == 0, rops.TRACE_COUNTS


def test_eager_add_trace_not_shared_across_mesh_contexts():
    """The eager ring-write jit cache must key on the active mesh rules:
    a mesh trainer tracing first must not bake its sharding constraints
    into a later meshless trainer's replay pushes (and vice versa)."""
    mesh = make_ac_mesh(1, 1)
    tr_m = SpreezeTrainer(_cfg(mesh=mesh))
    tr_m._warmup()                  # traces the eager add under rules
    tr_r = SpreezeTrainer(_cfg())   # same shapes, no mesh
    tr_r._warmup()
    sh = tr_r.replay.rows.sharding
    mesh_names = set(getattr(getattr(sh, "mesh", None), "axis_names", ()))
    assert mesh_names != {"ac", "batch"}, (
        "meshless trainer's replay got committed onto the mesh trainer's "
        "mesh via a shared jit trace")


def test_mesh_requires_ac_batch_axes():
    mesh = make_debug_mesh(1, 1)
    with pytest.raises(ValueError, match="ac"):
        SpreezeTrainer(_cfg(mesh=mesh))


def test_mesh_requires_fused_path():
    mesh = make_ac_mesh(1, 1)
    with pytest.raises(ValueError):
        SpreezeTrainer(_cfg(mesh=mesh, sync_mode=True))


def test_mesh_capacity_divisibility():
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices for a batch axis of size 2")
    mesh = make_ac_mesh(1, 2)
    with pytest.raises(ValueError, match="divisible"):
        SpreezeTrainer(_cfg(mesh=mesh, replay_capacity=257))


def test_overlap_eval_requires_fused():
    with pytest.raises(ValueError, match="overlap_eval"):
        SpreezeTrainer(_cfg(overlap_eval=True, fused=False))


def test_overlap_eval_snapshot_feeds_eval():
    tr = SpreezeTrainer(_cfg(overlap_eval=True, rounds_per_dispatch=2))
    tr._warmup()
    (tr.state, tr.replay, tr.env_states, tr.key,
     tr.last_metrics) = tr._megastep(tr.state, tr.replay, tr.env_states,
                                     tr.key)
    import numpy as np
    snap = tr.last_metrics["actor_snapshot"]
    for a, b in zip(jax.tree.leaves(snap), jax.tree.leaves(tr.state.actor)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # eval consumes the snapshot, not the live (soon-donated) state
    actor = tr._actor_for_eval()
    assert actor is snap
