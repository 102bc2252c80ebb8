"""Plain reference networks of SAC (Haarnoja et al., arXiv:1812.05905):
a tanh-Gaussian actor and twin Q towers, MLPs with ReLU over the
current observation. The default networks of a configuration that names
none.

A networks module is found by the ``networks`` key of a configuration
(``networks_<name>.py`` beside this file is ``"networks": "<name>"``)
and supplies:

- ``GROUPS``, the groups of trained parameters, each with its own Adam
  state; ``CRITIC``, those the critic loss trains, each with a target
  copy; ``init(key, obs_dim, act_dim, hp, dtype) -> {group: params}``;
- the forward passes ``sample_action(params, obs, key) -> (action,
  logp)`` and ``q_values(params, obs, act) -> (2, B)``, with ``params``
  a dict that holds the groups;
- the rollout: ``policy_state(cfg, n, dtype)``, the policy's state of
  each of ``n`` envs at the start; ``act(params, policy_state, obs,
  key) -> (action, logp, policy_state)``; and ``step_end(policy_state,
  done)``, after the env step, ``done`` marking the envs whose episode
  ended (their next observation starts a new one);
- ``batch(data, idx, cfg, mix)``: the training example of the sampled
  ring rows ``idx`` (``data``: the ring's fields, rows written
  chunk by chunk, time-major, ``num_envs`` to a step);
- the counts kept with the benchmark: ``flops_per_env_frame(cfg, mix)``
  and ``rows_per_sample(cfg, mix)``.

Weights start as N(0, 1/fan_in), biases 0, in ``dtype``. The key is
split as the program splits it, so the same seed draws the same weights.
"""
import math

import jax
import jax.numpy as jnp

LOG_STD_MIN, LOG_STD_MAX = -20.0, 2.0
GROUPS = ("actor", "q")
CRITIC = ("q",)


def mlp_init(key, dims, dtype):
    keys = jax.random.split(key, len(dims) - 1)
    return [{"w": (jax.random.normal(keys[i], (dims[i], dims[i + 1]))
                   * (1.0 / math.sqrt(dims[i]))).astype(dtype),
             "b": jnp.zeros((dims[i + 1],), dtype)}
            for i in range(len(dims) - 1)]


def mlp(layers, x):
    for i, l in enumerate(layers):
        x = x @ l["w"] + l["b"]
        if i < len(layers) - 1:
            x = jnp.maximum(x, 0)
    return x


def towers_init(key, in_dim, act_dim, hidden, dtype):
    """-> {"actor": in_dim -> 2 act_dim, "q": two (in_dim + act_dim) -> 1
    towers, stacked}"""
    ka, kq = jax.random.split(key)
    h = list(hidden)
    return {"actor": mlp_init(ka, [in_dim] + h + [2 * act_dim], dtype),
            "q": jax.vmap(lambda k: mlp_init(k, [in_dim + act_dim] + h + [1],
                                             dtype))(jax.random.split(kq, 2))}


def init(key, obs_dim, act_dim, hp, dtype):
    return towers_init(key, obs_dim, act_dim, hp["hidden"], dtype)


def squashed(head, key):
    """The actor head's (mean, log std) -> (tanh-squashed action, its
    log-probability)"""
    mean, log_std = jnp.split(head, 2, axis=-1)
    log_std = jnp.clip(log_std, LOG_STD_MIN, LOG_STD_MAX)
    eps = jax.random.normal(key, mean.shape).astype(mean.dtype)
    a = jnp.tanh(mean + jnp.exp(log_std) * eps)
    logp = (-0.5 * eps ** 2 - log_std - 0.5 * math.log(2 * math.pi)).sum(-1)
    return a, logp - jnp.log(jnp.clip(1 - a ** 2, 1e-6)).sum(-1)


def twin_q(q, x, act):
    """-> (2, B): both towers"""
    x = jnp.concatenate([x, act], axis=-1)
    return jax.vmap(lambda p: mlp(p, x)[..., 0])(q)


def sample_action(params, obs, key):
    return squashed(mlp(params["actor"], obs), key)


def q_values(params, obs, act):
    return twin_q(params["q"], obs, act)


def policy_state(cfg, n, dtype):
    """The MLP sees the current observation alone: no state."""
    return ()


def act(params, policy_state, obs, key):
    a, logp = sample_action(params, obs, key)
    return a, logp, policy_state


def step_end(policy_state, done):
    return policy_state


def batch(data, idx, cfg, mix):
    """One ring row a sample."""
    return {k: v[idx] for k, v in data.items()}


def rows_per_sample(cfg, mix):
    return 1


def mlp_macs(dims):
    return sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def tower_macs(cfg):
    """-> (actor MACs, one Q tower's MACs) per row."""
    h = list(cfg["hidden"])
    obs, act = cfg["obs_dim"], cfg["act_dim"]
    return (mlp_macs([obs] + h + [2 * act]),
            mlp_macs([obs + act] + h + [1]))


def update_flops_per_sample(cfg):
    """FLOPs (2 a multiply-add) of one SAC update of one sampled row, in
    forward-pass equivalents: the actor 4 times, a Q tower 12 times.
    Critic: actor forward on next_obs (1 actor); both target towers
    (2 Q); both online towers forward and backward (2 + 4 Q). Actor:
    actor forward and backward (1 + 2 actor); both towers forward and
    backward to the action only (2 + 2 Q)."""
    actor, q = tower_macs(cfg)
    return 2 * (4 * actor + 12 * q)


def flops_per_env_frame(cfg, mix):
    """Training FLOPs per env frame sampled: the policy forward plus the
    updates, UTD (rows updated per frame) of them. Eval episodes are not
    training work and are not counted."""
    utd = (mix["updates_per_round"] * mix["batch_size"]
           / (mix["num_envs"] * mix["chunk_len"]))
    return 2 * tower_macs(cfg)[0] + utd * update_flops_per_sample(cfg)
