"""Plain reference of SAC (Haarnoja et al., arXiv:1812.05905): a
tanh-Gaussian actor and twin Q towers, both MLPs with ReLU, target
towers by Polyak averaging, a learned temperature, Adam on each part.

Every floating array is held in ``dtype`` (float32 for the reference,
bfloat16 for the control); weights start as N(0, 1/fan_in), biases 0,
log alpha at log(init_alpha). The key is split as the program splits
it, so the same seed draws the same noise.
"""
import math

import jax
import jax.numpy as jnp

LOG_STD_MIN, LOG_STD_MAX = -20.0, 2.0
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


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


def adam_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"t": jnp.zeros((), jnp.int32), "m": zeros,
            "v": jax.tree.map(jnp.zeros_like, params)}


def adam_step(params, grads, opt, lr):
    t = opt["t"] + 1
    m = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g,
                     opt["m"], grads)
    v = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g,
                     opt["v"], grads)
    tf = t.astype(jnp.float32)
    c1, c2 = 1 - ADAM_B1 ** tf, 1 - ADAM_B2 ** tf
    new = jax.tree.map(
        lambda p, m, v: (p - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS)
                         ).astype(p.dtype), params, m, v)
    return new, {"t": t, "m": m, "v": v}


def init(key, obs_dim, act_dim, hp, dtype):
    ka, kq = jax.random.split(key)
    h = list(hp["hidden"])
    actor = mlp_init(ka, [obs_dim] + h + [2 * act_dim], dtype)
    q = jax.vmap(lambda k: mlp_init(k, [obs_dim + act_dim] + h + [1],
                                    dtype))(jax.random.split(kq, 2))
    log_alpha = jnp.log(jnp.asarray(hp["init_alpha"], jnp.float32)
                        ).astype(dtype)
    return {"actor": actor, "q": q, "q_target": q, "log_alpha": log_alpha,
            "opt_actor": adam_init(actor), "opt_q": adam_init(q),
            "opt_alpha": adam_init(log_alpha)}


def sample_action(actor, obs, key):
    """-> (tanh-squashed action, its log-probability)"""
    mean, log_std = jnp.split(mlp(actor, obs), 2, axis=-1)
    log_std = jnp.clip(log_std, LOG_STD_MIN, LOG_STD_MAX)
    eps = jax.random.normal(key, mean.shape).astype(mean.dtype)
    a = jnp.tanh(mean + jnp.exp(log_std) * eps)
    logp = (-0.5 * eps ** 2 - log_std - 0.5 * math.log(2 * math.pi)).sum(-1)
    return a, logp - jnp.log(jnp.clip(1 - a ** 2, 1e-6)).sum(-1)


def q_values(q, obs, act):
    """-> (2, B): both towers"""
    x = jnp.concatenate([obs, act], axis=-1)
    return jax.vmap(lambda p: mlp(p, x)[..., 0])(q)


def update(s, batch, key, hp, act_dim):
    """One SAC step on ``batch`` -> (state', critic loss, per-row |TD|)."""
    k1, k2 = jax.random.split(key)
    alpha = jnp.exp(s["log_alpha"])
    na, nlogp = sample_action(s["actor"], batch["next_obs"], k1)
    q_next = q_values(s["q_target"], batch["next_obs"], na).min(0)
    target = jax.lax.stop_gradient(
        batch["rew"] + batch["disc"] * (q_next - alpha * nlogp))
    w = batch.get("weight")

    def critic_loss(q):
        err = q_values(q, batch["obs"], batch["act"]) - target[None]
        se = err ** 2 if w is None else err ** 2 * w[None]
        return se.mean(), jnp.abs(err).mean(0)

    (closs, td), gq = jax.value_and_grad(critic_loss, has_aux=True)(s["q"])
    q, opt_q = adam_step(s["q"], gq, s["opt_q"], hp["lr"])

    def actor_loss(actor):
        a, logp = sample_action(actor, batch["obs"], k2)
        return (alpha * logp - q_values(q, batch["obs"], a).min(0)).mean(), \
            logp.mean()

    (_, logp_mean), ga = jax.value_and_grad(actor_loss, has_aux=True)(
        s["actor"])
    actor, opt_actor = adam_step(s["actor"], ga, s["opt_actor"], hp["lr"])
    # d/d(log alpha) of -log_alpha * (mean log pi + target entropy)
    g_alpha = (-(logp_mean - hp["target_entropy_scale"] * act_dim)
               ).astype(s["log_alpha"].dtype)
    log_alpha, opt_alpha = adam_step(s["log_alpha"], g_alpha,
                                     s["opt_alpha"], hp["lr"])
    tau = hp["tau"]
    q_target = jax.tree.map(lambda t, o: ((1 - tau) * t + tau * o
                                          ).astype(t.dtype),
                            s["q_target"], q)
    return ({"actor": actor, "q": q, "q_target": q_target,
             "log_alpha": log_alpha, "opt_actor": opt_actor, "opt_q": opt_q,
             "opt_alpha": opt_alpha}, closs, td)


#: the trained leaves whose change and first moment are compared
TRAINED = ("actor", "q", "log_alpha")
FIRST_MOMENT = {"actor": "opt_actor", "q": "opt_q", "log_alpha": "opt_alpha"}
