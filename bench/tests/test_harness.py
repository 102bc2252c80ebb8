"""The harness without a chip: the look for a TPU, the result line, and
the check that decides ``correct`` against a broken timed path and
against the control, at a size a CPU test run holds."""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import compare
import harness
from catalog import Catalog
from reference import pipeline

CAT = Catalog()
CELLS = [c["name"] for c in CAT.spec["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "breakdown", "checks"}


def small(name):
    """The cell at a size a CPU test holds: widths as published, fewer
    envs, a smaller batch, ring and round."""
    cell, cfg, mix, limits = CAT.workload(name)
    cfg = dict(cfg, replay_capacity=4096)
    mix = dict(mix, num_envs=16, chunk_len=8, batch_size=64,
               updates_per_round=3, rounds_per_dispatch=2)
    return cell, cfg, mix, limits


def run_small(name, seed=2**31 + 5):
    cell, cfg, mix, limits = small(name)
    return harness.run_cell(CAT, cell, cfg, mix, limits, seed, 0.5, False,
                            jax.devices()[:1], time.perf_counter())


def test_no_tpu_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(CAT.bench, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=CAT.root, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("name", CELLS)
def test_result_line_has_only_the_contract_keys(name, capsys):
    """A sound run at test size is correct, and its last line holds the
    contract's keys, the compared numbers last."""
    result = run_small(name)
    harness.emit(result)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert set(line) <= RESULT_KEYS
    assert set(line) >= RESULT_KEYS - {"breakdown"}
    assert list(line)[-1] == "checks"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["metrics"]) == {"env_frames_per_s", "setup_s"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    limits = CAT.workload(name)[3]
    for n in limits:
        assert f"check {n}:" in err
    tail = err.strip().splitlines()[-len(limits):]
    assert all(t.startswith("check ") for t in tail)


def _state_unchanged(monkeypatch):
    """The megastep runs but hands back the state it was given."""
    from repro.core import pipeline as core
    build = core.SpreezeTrainer._build_compiled

    def broken(self):
        build(self)
        step = self._megastep

        def unchanged(state, replay, env_states, key):
            out = step(jax.tree.map(jnp.copy, state), replay, env_states,
                       key)
            return (state,) + tuple(out[1:])
        self._megastep = unchanged
    monkeypatch.setattr(core.SpreezeTrainer, "_build_compiled", broken)


def _half_batch(monkeypatch):
    """Each update gets the first half of the rows it drew, and the
    update's mean runs over those."""
    from repro.replay import buffer as rb
    from repro.replay import prioritized as per
    sample, per_sample = rb.sample, per.sample

    def half(state, key, batch_size, **kw):
        batch = sample(state, key, batch_size, **kw)
        return {k: v[:batch_size // 2] for k, v in batch.items()}

    def per_half(state, key, batch_size, **kw):
        batch, idx, w = per_sample(state, key, batch_size, **kw)
        h = batch_size // 2
        return {k: v[:h] for k, v in batch.items()}, idx[:h], w[:h]
    monkeypatch.setattr(rb, "sample", half)
    monkeypatch.setattr(per, "sample", per_half)


def _skip_reprioritise(monkeypatch):
    """The drawn rows keep the priorities they had."""
    from repro.replay import prioritized as per
    monkeypatch.setattr(per, "update_priorities",
                        lambda state, idx, td, **kw: state)


def _unit_weights(monkeypatch):
    """Prioritized draws with every importance weight 1."""
    from repro.replay import prioritized as per
    sample = per.sample

    def unit(state, key, batch_size, **kw):
        batch, idx, w = sample(state, key, batch_size, **kw)
        return batch, idx, jnp.ones_like(w)
    monkeypatch.setattr(per, "sample", unit)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "skip_reprioritise": _skip_reprioritise,
          "unit_weights": _unit_weights}
PER_ONLY = {"skip_reprioritise", "unit_weights"}


@pytest.mark.parametrize("name,fault", [
    (name, fault) for name in CELLS for fault in FAULTS
    if fault not in PER_ONLY or CAT.workload(name)[2]["prioritized"]])
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    """A run whose megastep is broken underneath comes out not correct,
    once for each fault the cell can have."""
    FAULTS[fault](monkeypatch)
    result = run_small(name)
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    """The reference computed in bfloat16, in the program's place, fails
    the cell's limits; the float32 reference against itself passes."""
    _, cfg, mix, limits = small(name)
    ref = pipeline.follow(cfg, mix, 7, harness.FOLLOWED)
    control = pipeline.follow(cfg, mix, 7, harness.FOLLOWED,
                              dtype=jnp.bfloat16)
    assert not compare.checks(compare.readings(control, ref), limits)[1]
    assert compare.checks(compare.readings(ref, ref), limits)[1]
