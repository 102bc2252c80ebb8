"""Plain reference of SAC (Haarnoja et al., arXiv:1812.05905): the
losses of a tanh-Gaussian actor and twin Q towers, target copies by
Polyak averaging, a learned temperature, Adam on each trained group.

The networks are a module's (``networks_mlp.py`` describes what one
supplies): its ``GROUPS`` each get their own Adam state (``opt_<group>``),
its ``CRITIC`` groups are trained by the critic loss and get a target
copy (``<group>_target``), and the actor group by the actor loss. Every
floating array is held in ``dtype`` (float32 for the reference, bfloat16
for the control); log alpha starts at log(init_alpha). The key is split
as the program splits it, so the same seed draws the same noise.
"""
import jax
import jax.numpy as jnp

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


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


def init(net, key, obs_dim, act_dim, hp, dtype):
    s = dict(net.init(key, obs_dim, act_dim, hp, dtype))
    s.update({f"{g}_target": s[g] for g in net.CRITIC})
    s["log_alpha"] = jnp.log(jnp.asarray(hp["init_alpha"], jnp.float32)
                             ).astype(dtype)
    s.update({f"opt_{g}": adam_init(s[g]) for g in net.GROUPS})
    s["opt_alpha"] = adam_init(s["log_alpha"])
    return s


def update(net, s, batch, key, hp, act_dim):
    """One SAC step on ``batch`` -> (state', critic loss, per-row |TD|)."""
    k1, k2 = jax.random.split(key)
    alpha = jnp.exp(s["log_alpha"])
    na, nlogp = net.sample_action(s, batch["next_obs"], k1)
    targets = dict(s, **{g: s[f"{g}_target"] for g in net.CRITIC})
    q_next = net.q_values(targets, batch["next_obs"], na).min(0)
    target = jax.lax.stop_gradient(
        batch["rew"] + batch["disc"] * (q_next - alpha * nlogp))
    w = batch.get("weight")

    def critic_loss(critic):
        err = net.q_values(dict(s, **critic), batch["obs"], batch["act"]) \
            - target[None]
        se = err ** 2 if w is None else err ** 2 * w[None]
        return se.mean(), jnp.abs(err).mean(0)

    (closs, td), grads = jax.value_and_grad(critic_loss, has_aux=True)(
        {g: s[g] for g in net.CRITIC})
    new = dict(s)
    for g in net.CRITIC:
        new[g], new[f"opt_{g}"] = adam_step(s[g], grads[g], s[f"opt_{g}"],
                                            hp["lr"])

    def actor_loss(actor):
        a, logp = net.sample_action(dict(new, actor=actor), batch["obs"], k2)
        return (alpha * logp - net.q_values(new, batch["obs"], a).min(0)
                ).mean(), logp.mean()

    (_, logp_mean), ga = jax.value_and_grad(actor_loss, has_aux=True)(
        s["actor"])
    new["actor"], new["opt_actor"] = adam_step(s["actor"], ga,
                                               s["opt_actor"], hp["lr"])
    # d/d(log alpha) of -log_alpha * (mean log pi + target entropy)
    g_alpha = (-(logp_mean - hp["target_entropy_scale"] * act_dim)
               ).astype(s["log_alpha"].dtype)
    new["log_alpha"], new["opt_alpha"] = adam_step(
        s["log_alpha"], g_alpha, s["opt_alpha"], hp["lr"])
    tau = hp["tau"]
    for g in net.CRITIC:
        new[f"{g}_target"] = jax.tree.map(
            lambda t, o: ((1 - tau) * t + tau * o).astype(t.dtype),
            s[f"{g}_target"], new[g])
    return new, closs, td


def first_moment(net):
    """{trained leaves: the Adam state that holds their moments}: the
    networks' groups and the temperature. These are the leaves whose
    change and moments are compared."""
    return {**{g: f"opt_{g}" for g in net.GROUPS}, "log_alpha": "opt_alpha"}
