"""``chip_smoke.py`` on the CPU: it must refuse to report without a TPU,
and its phases must hold their own checks at a tiny size (with the
Mosaic ``tpu_custom_call`` check off — interpret mode has none)."""
import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

TINY = dict(num_envs=64, chunk_len=4, batch_size=32, replay_capacity=4096,
            updates_per_round=2, rounds_per_dispatch=2)


def test_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


@pytest.fixture
def no_kernel_check(monkeypatch):
    monkeypatch.setattr(chip_smoke, "_check_kernels", lambda *a, **k: None)


def test_one_chip_phases_at_tiny_size(no_kernel_check, capsys):
    out = chip_smoke.run(1, TINY)
    assert out["ok"] and out["device"]["count"] == len(jax.devices())
    phases = [json.loads(line)["smoke"]
              for line in capsys.readouterr().out.splitlines()]
    assert phases == ["a-uniform-pendulum", "b-per-nstep3-hopper",
                      "c-pallas-vs-jnp-ring", "c-pallas-vs-jnp-per-hopper",
                      "d-per-topk-vs-dense"]


@pytest.mark.parametrize("fault", ["order", "tie"])
def test_per_topk_phase_catches_a_wrong_selection(monkeypatch, fault):
    """A selection that swaps two draws, or breaks a tie by the higher
    index, must fail phase d."""
    import jax.numpy as jnp

    from repro.kernels import replay_ops as rops
    per_topk = rops.per_topk

    def wrong(p, g, alpha, k, **kw):
        v, i, pri = per_topk(p, g, alpha, k, **kw)
        if fault == "order":
            return v, jnp.roll(i, 1), pri
        # among equal scores, prefer the later row: reversed window
        v, i, pri = per_topk(p[::-1], g[::-1], alpha, k, **kw)
        return v, p.shape[0] - 1 - i, pri
    monkeypatch.setattr(rops, "per_topk", wrong)
    with pytest.raises(chip_smoke.SmokeFailure, match="differ"):
        chip_smoke.per_topk_phase("d", TINY)


def test_pallas_vs_jnp_catches_a_wrong_per_kernel(no_kernel_check,
                                                  monkeypatch):
    """A priority scatter that drops one update must fail phase c."""
    from repro.kernels import ops as kops
    scatter = kops.priority_scatter
    monkeypatch.setattr(kops, "priority_scatter",
                        lambda p, i, v, **kw: scatter(p, i[1:], v[1:], **kw))
    with pytest.raises(chip_smoke.SmokeFailure, match="differ"):
        chip_smoke.pallas_vs_jnp_phase("c", TINY, **chip_smoke.PER)


def test_four_chip_mesh_phase_at_tiny_size(no_kernel_check):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (forced host devices)")
    from repro.launch.mesh import make_ac_mesh
    chip_smoke.mesh_phase("mesh", TINY, make_ac_mesh(2, 2),
                          **chip_smoke.PER)


def test_mesh_phase_catches_a_wrong_candidate_merge(no_kernel_check,
                                                    monkeypatch):
    """A cross-group PER merge that picks the wrong candidates must fail
    the mesh phase's draw check."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (forced host devices)")
    import jax.numpy as jnp

    from repro.kernels import replay_ops as rops
    from repro.launch.mesh import make_ac_mesh

    def merge(scores, idx, pri, k):
        v, pos = jax.lax.top_k(scores, k)
        pos = jnp.roll(pos, 1)
        return v, jnp.take(idx, pos), jnp.take(pri, pos)
    monkeypatch.setattr(rops, "merge_topk_candidates", merge)
    with pytest.raises(chip_smoke.SmokeFailure, match="different indices"):
        chip_smoke.mesh_phase("mesh", TINY, make_ac_mesh(2, 2),
                              **chip_smoke.PER)


def test_phase_fails_on_supervisor_fault(no_kernel_check):
    """A worker restart recorded by the supervisor fails the phase."""
    from repro.core import faults
    plan = faults.FaultPlan(eval_error_rounds=(0,))
    with pytest.raises(chip_smoke.SmokeFailure, match="supervisor"):
        chip_smoke.train_phase("fault", TINY, fault_plan=plan,
                               **chip_smoke.UNIFORM)
