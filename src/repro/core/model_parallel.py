"""Actor-Critic model parallelism over the mesh (paper §3.2.2, Fig. 2b/3).

The paper places the actor network on GPU0 and the double-Q critics (+
targets) on GPU1, routing each experience field only to the device that
consumes it. The TPU-native generalization (DESIGN.md §2):

* the double-Q ensemble is a stacked leading axis of size 2 sharded over
  the ``ac`` mesh axis (multi-pod: the **pod** axis) — each pod updates one
  Q tower with zero gradient exchange;
* the actor's params stay on ac-group 0 (replicated cheaply — MLP towers
  are tiny relative to experience);
* the cross-``ac`` traffic is exactly the paper's: the (B,)-sized
  ``min(Q1,Q2)`` tensors, not gradients or weights.

This module provides the sharding specs + the jit-able RL update entry
used by the multi-pod dry-run, for both MLP towers and assigned-arch
backbone towers (RLHF-scale Spreeze).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import (MeshRules, current_rules,
                                        params_sharding_tree, spreeze_rules,
                                        use_rules)
from repro.rl import networks as nets
from repro.rl.base import AlgoHP, AlgoState, get_algo


# ---------------------------------------------------------------------------
# sharding specs for the AlgoState / batch under spreeze rules
# ---------------------------------------------------------------------------

def ensemble_sharding(tree, rules: MeshRules):
    """Leading dim -> ``ac`` axis; remaining dims unsharded (MLP towers)."""
    def one(leaf):
        return NamedSharding(rules.mesh,
                             P(rules.ac, *([None] * (leaf.ndim - 1))))
    return jax.tree.map(one, tree)


def replicated_sharding(tree, rules: MeshRules):
    return jax.tree.map(
        lambda leaf: NamedSharding(rules.mesh, P()), tree)


def batch_sharding(batch, rules: MeshRules):
    """Experience rows over the data axis (each pod group reads its shard;
    rew/done route with the critic fields automatically under GSPMD)."""
    def one(leaf):
        return NamedSharding(rules.mesh,
                             P(rules.batch, *([None] * (leaf.ndim - 1))))
    return jax.tree.map(one, batch)


def algo_state_sharding(state: AlgoState, rules: MeshRules) -> AlgoState:
    """NamedSharding pytree for jit in_shardings of the update step."""
    def opt_like(params_shardings, opt_state):
        # OptState(step, mu, nu) mirrors params in mu/nu
        if opt_state is None:
            return None
        return type(opt_state)(
            step=NamedSharding(rules.mesh, P()),
            mu=jax.tree.map(lambda _, s: s, opt_state.mu, params_shardings),
            nu=(jax.tree.map(lambda _, s: s, opt_state.nu, params_shardings)
                if jax.tree.structure(opt_state.nu)
                == jax.tree.structure(params_shardings)
                else jax.tree.map(
                    lambda l: NamedSharding(rules.mesh, P()), opt_state.nu)))

    actor_sh = replicated_sharding(state.actor, rules)
    q_sh = ensemble_sharding(state.q, rules)
    tgt_sh = jax.tree.map(
        lambda l: (NamedSharding(rules.mesh,
                                 P(rules.ac, *([None] * (l.ndim - 1))))),
        state.q_target) if _is_pure_ensemble(state.q_target, state.q) else \
        _mixed_target_sharding(state.q_target, rules)
    scalar = NamedSharding(rules.mesh, P())
    return AlgoState(
        actor=actor_sh, q=q_sh, q_target=tgt_sh, log_alpha=scalar,
        opt_actor=opt_like(actor_sh, state.opt_actor),
        opt_q=opt_like(q_sh, state.opt_q),
        opt_alpha=(opt_like(scalar, state.opt_alpha)
                   if state.opt_alpha is not None else None),
        step=scalar)


def _is_pure_ensemble(tgt, q) -> bool:
    return jax.tree.structure(tgt) == jax.tree.structure(q)


def _mixed_target_sharding(tgt, rules: MeshRules):
    """TD3/DDPG target holder {"q": ensemble, "actor": replicated}."""
    return {
        "q": jax.tree.map(
            lambda l: NamedSharding(rules.mesh,
                                    P(rules.ac, *([None] * (l.ndim - 1)))),
            tgt["q"]),
        "actor": jax.tree.map(
            lambda l: NamedSharding(rules.mesh, P()), tgt["actor"]),
    }


# ---------------------------------------------------------------------------
# dry-run entry: the Spreeze update step on the production mesh
# ---------------------------------------------------------------------------

def make_spreeze_update(mesh: Mesh, *, algo: str = "sac",
                        obs_dim: int = 26, act_dim: int = 6,
                        batch_size: int = 8192,
                        hp: Optional[AlgoHP] = None,
                        placement: str = "ac"):
    """Returns (update_fn, state_shapes, batch_shapes, in_shardings) for
    ``jax.jit(update_fn, in_shardings=...).lower(...)`` on the mesh.

    placement="ac" (paper Fig. 2b): the double-Q ensemble axis maps to the
    pod axis — each pod owns one critic, no cross-pod gradients.
    placement="dp" (paper Fig. 2a baseline): everything replicated over
    pods, batch sharded over (pod, data) — gradients all-reduce across
    pods. The dry-run compares the cross-pod collective bytes of the two.
    """
    hp = hp or AlgoHP(algo=algo)
    if placement == "dp":
        rules = standard_rules_for_rl(mesh)
    else:
        rules = spreeze_rules(mesh)
        if rules.ac is None:      # single-pod mesh: borrow the data axis
            rules = MeshRules(mesh=mesh, batch=("data",), seq=rules.seq,
                              fsdp=rules.fsdp, tp=rules.tp, ac="data")
    mod = get_algo(algo)

    with use_rules(rules):
        state = jax.eval_shape(
            lambda k: mod.init_state(k, obs_dim, act_dim, hp),
            jax.random.PRNGKey(0))
    update = mod.make_update_step(hp, obs_dim, act_dim)

    def update_fn(state, batch, key):
        with use_rules(rules):
            return update(state, batch, key)

    batch_shapes = {
        "obs": jax.ShapeDtypeStruct((batch_size, obs_dim), jnp.float32),
        "act": jax.ShapeDtypeStruct((batch_size, act_dim), jnp.float32),
        "rew": jax.ShapeDtypeStruct((batch_size,), jnp.float32),
        "next_obs": jax.ShapeDtypeStruct((batch_size, obs_dim), jnp.float32),
        "done": jax.ShapeDtypeStruct((batch_size,), jnp.float32),
    }
    # materialize state shapes via eval_shape on init
    in_shardings = (
        _state_shardings_from_shapes(state, rules),
        batch_sharding(batch_shapes, rules),
        NamedSharding(mesh, P()),
    )
    key_shape = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return update_fn, state, batch_shapes, key_shape, in_shardings


def standard_rules_for_rl(mesh: Mesh) -> MeshRules:
    """Fig. 2a data parallelism: no ac axis; batch over every data-ish
    axis; params replicated (MLP towers are tiny — FSDP would only add
    gathers)."""
    names = mesh.axis_names
    batch = tuple(a for a in ("pod", "data") if a in names)
    return MeshRules(mesh=mesh, batch=batch or ("data",), seq=None,
                     fsdp=None, tp=None, ac=None)


def _state_shardings_from_shapes(state: AlgoState, rules: MeshRules):
    """Like algo_state_sharding but works on ShapeDtypeStruct pytrees."""
    def ens(l):
        if l.ndim == 0 or rules.ac is None:     # opt step counters etc.
            return NamedSharding(rules.mesh, P())
        return NamedSharding(rules.mesh, P(rules.ac,
                                           *([None] * (l.ndim - 1))))

    def rep(l):
        return NamedSharding(rules.mesh, P())

    tgt = (jax.tree.map(ens, state.q_target)
           if jax.tree.structure(state.q_target)
           == jax.tree.structure(state.q)
           else {"q": jax.tree.map(ens, state.q_target["q"]),
                 "actor": jax.tree.map(rep, state.q_target["actor"])})
    return AlgoState(
        actor=jax.tree.map(rep, state.actor),
        q=jax.tree.map(ens, state.q),
        q_target=tgt,
        log_alpha=rep(state.log_alpha),
        opt_actor=jax.tree.map(rep, state.opt_actor),
        opt_q=jax.tree.map(ens, state.opt_q),
        opt_alpha=(jax.tree.map(rep, state.opt_alpha)
                   if state.opt_alpha is not None else None),
        step=rep(state.step))


# ---------------------------------------------------------------------------
# arch-backbone Spreeze towers (RLHF-scale): actor LM on pod0, critic on pod1
# ---------------------------------------------------------------------------

def make_arch_spreeze_losses(cfg: ModelConfig, act_dim: int = 16,
                             dtype=jnp.bfloat16,
                             hp: Optional[AlgoHP] = None):
    """Actor/critic loss fns whose towers are assigned-arch backbones.

    Used by the dry-run to prove the paper's technique composes with the
    large architectures: actor tower sharded over (data, model) within
    pod 0's groups, the two critic towers over the ``ac``(=pod) axis.

    ``critic_loss`` mirrors ``rl/sac.py``: the TD target is built from
    the *target* critic params and wrapped in ``stop_gradient`` so no
    gradient flows through the bootstrap, with ``hp.gamma`` as the
    discount.
    """
    hp = hp or AlgoHP()
    def actor_loss(actor_params, q_params, tokens, key):
        mean, log_std = nets.arch_policy_dist(actor_params, tokens, cfg,
                                              dtype=dtype)
        std = jnp.exp(log_std)
        eps = jax.random.normal(key, mean.shape)
        a = jnp.tanh(mean + std * eps)
        logp = (-0.5 * eps ** 2 - log_std
                - 0.5 * jnp.log(2 * jnp.pi)).sum(-1)
        logp = logp - jnp.log(jnp.clip(1 - a ** 2, 1e-6)).sum(-1)
        q = jax.vmap(
            lambda qp: nets.arch_q_value(qp, tokens, a, cfg, dtype=dtype)
        )(q_params).min(axis=0)
        return jnp.mean(0.2 * logp - q)

    def critic_loss(q_params, q_target_params, actor_params, tokens, act,
                    rew, done, key):
        q_pred = jax.vmap(
            lambda qp: nets.arch_q_value(qp, tokens, act, cfg, dtype=dtype)
        )(q_params)
        mean, log_std = nets.arch_policy_dist(actor_params, tokens, cfg,
                                              dtype=dtype)
        a2 = jnp.tanh(mean)
        q_next = jax.vmap(
            lambda qp: nets.arch_q_value(qp, tokens, a2, cfg, dtype=dtype)
        )(q_target_params).min(axis=0)
        target = jax.lax.stop_gradient(
            rew + hp.gamma * (1 - done) * q_next)
        return jnp.mean((q_pred - target[None]) ** 2)

    return actor_loss, critic_loss


# ---------------------------------------------------------------------------
# sharded-megastep specs: replay ring + env states on the trainer mesh
# ---------------------------------------------------------------------------

def replay_sharding(replay, rules: MeshRules):
    """NamedSharding pytree for the replay ring: the (capacity, width)
    record array shards its rows over the ``batch`` axis (each group owns
    a slice of the pool; scatter/gather stay group-local under GSPMD), the
    ring bookkeeping scalars replicate. Handles both the uniform
    ``ReplayState`` and the PER ``PrioritizedState`` wrapper."""
    rep = NamedSharding(rules.mesh, P())
    if hasattr(replay, "base"):            # PrioritizedState
        from repro.replay.prioritized import PrioritizedState
        return PrioritizedState(
            base=replay_sharding(replay.base, rules),
            priorities=NamedSharding(rules.mesh, P(rules.batch)),
            max_priority=rep)
    return dataclasses.replace(
        replay, rows=NamedSharding(rules.mesh, P(rules.batch, None)),
        ptr=rep, size=rep)
