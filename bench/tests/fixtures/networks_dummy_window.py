"""A networks module as a later configuration would bring it, for the
test that adding one is a files-only change: a linear encoder of a
2-frame window, trained by the critic loss alone (as in SAC-AE,
arXiv:1910.01741), under the MLP towers of ``networks_mlp``.

The rollout state is each env's previous observation (zeros at an
episode's start). A sample's window is its ring row and the row of the
same env one step before (``num_envs`` rows back, zeros across an
episode's start); the window of ``next_obs`` is (obs, next_obs), right
for 1-step returns.
"""
import jax
import jax.numpy as jnp

from reference import networks_mlp as mlp

GROUPS = ("actor", "q", "encoder")
CRITIC = ("q", "encoder")
WINDOW, FEATURES = 2, 16


def init(key, obs_dim, act_dim, hp, dtype):
    ke, kt = jax.random.split(key)
    return {"encoder": mlp.mlp_init(ke, [WINDOW * obs_dim, FEATURES], dtype),
            **mlp.towers_init(kt, FEATURES, act_dim, hp["hidden"], dtype)}


def encode(params, window):
    x = window.reshape(window.shape[:-2] + (-1,))
    return jnp.tanh(mlp.mlp(params["encoder"], x))


def sample_action(params, window, key):
    feats = jax.lax.stop_gradient(encode(params, window))
    return mlp.squashed(mlp.mlp(params["actor"], feats), key)


def q_values(params, window, act):
    return mlp.twin_q(params["q"], encode(params, window), act)


def policy_state(cfg, n, dtype):
    return jnp.zeros((n, cfg["obs_dim"]), dtype)


def act(params, policy_state, obs, key):
    a, logp = sample_action(params, jnp.stack([policy_state, obs], 1), key)
    return a, logp, obs


def step_end(policy_state, done):
    return jnp.where(done[:, None], 0, policy_state).astype(policy_state.dtype)


def batch(data, idx, cfg, mix):
    prev = (idx - mix["num_envs"]) % data["obs"].shape[0]
    before = jnp.where((data["done"][prev] > 0)[:, None], 0,
                       data["obs"][prev])
    out = {k: v[idx] for k, v in data.items()}
    out["obs"] = jnp.stack([before, out["obs"]], 1)
    out["next_obs"] = jnp.stack([out["obs"][:, 1], out["next_obs"]], 1)
    return out


def rows_per_sample(cfg, mix):
    return WINDOW


def flops_per_env_frame(cfg, mix):
    """The towers' count over the features, and the encoder's: one
    forward a frame for the policy; per sampled row seven forward
    equivalents: the next window through the online and the target
    encoder, the current window forward and backward (1 + 2), and twice
    forward in the actor loss."""
    enc = WINDOW * cfg["obs_dim"] * FEATURES
    utd = (mix["updates_per_round"] * mix["batch_size"]
           / (mix["num_envs"] * mix["chunk_len"]))
    return (mlp.flops_per_env_frame(dict(cfg, obs_dim=FEATURES), mix)
            + 2 * enc * (1 + 7 * utd))
