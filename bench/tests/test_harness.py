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


def legacy_program_config(cfg, mix, seed):
    """The settings as the harness mapped them key by key before it
    mapped them by field name."""
    from repro.core import SpreezeConfig
    from repro.rl.base import AlgoHP
    return SpreezeConfig(
        env_name=cfg["env"], algo=cfg["algo"],
        replay_capacity=cfg["replay_capacity"],
        num_envs=mix["num_envs"], chunk_len=mix["chunk_len"],
        batch_size=mix["batch_size"],
        updates_per_round=mix["updates_per_round"],
        rounds_per_dispatch=mix["rounds_per_dispatch"],
        warmup_frames=mix["warmup_frames"],
        prioritized=mix["prioritized"],
        per_alpha=mix.get("per_alpha", 0.6),
        per_beta=mix.get("per_beta", 0.4), nstep=mix["nstep"],
        eval_every_rounds=mix["eval_every_rounds"],
        eval_episodes=mix["eval_episodes"],
        use_pallas=True, seed=seed,
        hp=AlgoHP(algo=cfg["algo"], gamma=cfg["gamma"], tau=cfg["tau"],
                  lr=cfg["lr"], hidden=tuple(cfg["hidden"]),
                  init_alpha=cfg["init_alpha"],
                  target_entropy_scale=cfg["target_entropy_scale"]))


@pytest.mark.parametrize("config,traffic", [
    (c, t) for c in CAT.names("configs") for t in CAT.names("traffic")])
def test_program_config_by_field_name_is_the_legacy_mapping(config,
                                                            traffic):
    """Every configuration under every mix builds the SpreezeConfig the
    key-by-key mapping built, field for field."""
    import dataclasses
    cfg = CAT._json("configs", f"{config}.json")
    mix = CAT._json("traffic", f"{traffic}.json")
    got = harness.program_config(cfg, mix, 2**31 + 3)
    want = legacy_program_config(cfg, mix, 2**31 + 3)
    for f in dataclasses.fields(want):
        assert getattr(got, f.name) == getattr(want, f.name), f.name
    for f in dataclasses.fields(want.hp):
        assert getattr(got.hp, f.name) == getattr(want.hp, f.name), f.name
        assert type(getattr(got.hp, f.name)) is type(
            getattr(want.hp, f.name)), f.name


@pytest.mark.parametrize("where,key,value", [
    ("config", "kv_lora_rank", 512), ("mix", "prefix_len", 4),
    ("config", "seed", 1), ("mix", "use_pallas", False),
    ("config", "env_name", "hopper"), ("mix", "lr", 1e-3)])
def test_unknown_or_doubled_key_is_an_error_that_names_it(where, key,
                                                          value):
    """A key that names no field a configuration may set, or one that
    the mix sets again, is refused, never dropped."""
    _, cfg, mix, _ = CAT.workload(CELLS[0])
    if where == "config":
        cfg = dict(cfg, **{key: value})
    else:
        mix = dict(mix, **{key: value})
    with pytest.raises(ValueError, match=repr(key)):
        harness.program_config(cfg, mix, 1)


def test_trained_leaves_follow_the_optimizer_fields():
    """The trained subtrees are found by their optimizer fields, an
    encoder with its own optimizer among them, and come out in the
    reference's layout: ``l<i>`` dicts as lists."""
    from typing import Any, NamedTuple

    class Opt(NamedTuple):
        step: Any
        mu: Any
        nu: Any

    class State(NamedTuple):
        actor: Any
        q: Any
        q_target: Any
        encoder: Any
        encoder_target: Any
        log_alpha: Any
        opt_actor: Any
        opt_q: Any
        opt_encoder: Any
        opt_alpha: Any
        step: Any

    def tower(scale):
        return {f"l{i}": {"w": jnp.full((2, 3), scale + i),
                          "b": jnp.full((3,), -scale - i)}
                for i in range(3)}

    def enc(scale):
        return {"embed": jnp.full((4,), scale),
                "layers": {"l0": {"wkv": jnp.full((2,), scale)},
                           "l1": {"wkv": jnp.full((2,), scale + 1)}}}

    opt = lambda t: Opt(0, jax.tree.map(lambda x: x * 10, t),
                        jax.tree.map(lambda x: x * 100, t))
    params = {"actor": tower(1.0), "q": tower(2.0), "encoder": enc(3.0),
              "log_alpha": jnp.asarray(0.5)}
    state = State(q_target=tower(9.0), encoder_target=enc(9.0), step=7,
                  opt_actor=opt(params["actor"]), opt_q=opt(params["q"]),
                  opt_encoder=opt(params["encoder"]),
                  opt_alpha=opt(params["log_alpha"]), **params)
    assert harness.trained_fields(state) == {
        "actor": "opt_actor", "q": "opt_q", "encoder": "opt_encoder",
        "log_alpha": "opt_alpha"}
    got = harness.trained(state)
    want = {"actor": [{"w": jnp.full((2, 3), 1.0 + i),
                       "b": jnp.full((3,), -1.0 - i)} for i in range(3)],
            "q": [{"w": jnp.full((2, 3), 2.0 + i),
                   "b": jnp.full((3,), -2.0 - i)} for i in range(3)],
            "encoder": {"embed": jnp.full((4,), 3.0),
                        "layers": [{"wkv": jnp.full((2,), 3.0)},
                                   {"wkv": jnp.full((2,), 4.0)}]},
            "log_alpha": jnp.asarray(0.5)}
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree.leaves(got), jax.tree.leaves(want)))
    first, second = harness.moments(state)
    assert jax.tree.structure(first) == jax.tree.structure(want)
    assert all(bool((a == 10 * b).all()) for a, b in zip(
        jax.tree.leaves(first), jax.tree.leaves(want)))
    assert all(bool((a == 100 * b).all()) for a, b in zip(
        jax.tree.leaves(second), jax.tree.leaves(want)))
    paths = {k for k, _ in compare.paths(got)}
    assert {"actor.2.w", "q.0.b", "encoder.layers.1.wkv", "encoder.embed",
            "log_alpha"} <= paths and len(paths) == 16

    class Orphan(NamedTuple):
        actor: Any
        opt_critic: Any
    with pytest.raises(ValueError, match="opt_critic"):
        harness.trained_fields(Orphan(1, 2))
