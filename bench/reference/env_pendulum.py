"""Plain reference of the ``pendulum`` env: Gym Pendulum swing-up.

obs = (cos th, sin th, thdot); torque = 2 * clip(a, -1, 1);
reward = -(angle(th)^2 + 0.1 thdot^2 + 0.001 torque^2); 200 steps.
Written from the Gym equations, not from the program's module.
"""
import jax
import jax.numpy as jnp

OBS_DIM, ACT_DIM, EPISODE_LEN = 3, 1, 200
MAX_SPEED, MAX_TORQUE, DT, G, M, L = 8.0, 2.0, 0.05, 10.0, 1.0, 1.0


def reset(key):
    k1, k2 = jax.random.split(key)
    return {"th": jax.random.uniform(k1, (), minval=-jnp.pi, maxval=jnp.pi),
            "thdot": jax.random.uniform(k2, (), minval=-1.0, maxval=1.0),
            "t": jnp.zeros((), jnp.int32)}


def observe(s):
    return jnp.stack([jnp.cos(s["th"]), jnp.sin(s["th"]), s["thdot"]])


def step(s, a):
    """-> (state', reward, done)"""
    th, thdot = s["th"], s["thdot"]
    u = jnp.clip(a[0], -1.0, 1.0) * MAX_TORQUE
    angle = ((th + jnp.pi) % (2 * jnp.pi)) - jnp.pi
    cost = angle ** 2 + 0.1 * thdot ** 2 + 0.001 * u ** 2
    thdot = thdot + (3 * G / (2 * L) * jnp.sin(th) + 3.0 / (M * L ** 2) * u) * DT
    thdot = jnp.clip(thdot, -MAX_SPEED, MAX_SPEED)
    t = s["t"] + 1
    return ({"th": th + thdot * DT, "thdot": thdot, "t": t}, -cost,
            t >= EPISODE_LEN)
