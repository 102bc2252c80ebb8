"""Plain reference of the ``hopper`` env: the repository's planar
one-leg hopper (not Gym Hopper): obs 8, act 2, 400 steps, ends on a fall.

State (x, vx, z, vz, leg, pitch, t). Leg extension integrates
2 * u_leg; a spring-damper ground force, boosted by thrust, acts while
the foot is below the ground; forward thrust only in contact, steered
by pitch; reward = vx + 0.5 - 0.05 |u|^2 - 5 on a fall.
"""
import jax
import jax.numpy as jnp

OBS_DIM, ACT_DIM, EPISODE_LEN = 8, 2, 400
DT, GRAVITY, LEG_REST = 0.02, 9.8, 1.0


def reset(key):
    k1, k2 = jax.random.split(key)
    zero = jnp.zeros(())
    return {"x": zero,
            "vx": jax.random.uniform(k1, (), minval=-0.1, maxval=0.1),
            "z": LEG_REST + jax.random.uniform(k2, (), minval=0.0,
                                               maxval=0.05),
            "vz": zero, "leg": zero, "pitch": zero,
            "t": jnp.zeros((), jnp.int32)}


def observe(s):
    return jnp.stack([s["z"], s["vx"] * 0.3, s["vz"] * 0.3, s["leg"],
                      s["pitch"], jnp.sin(s["pitch"]),
                      jnp.clip(s["z"] - LEG_REST, -1, 1),
                      (s["t"] % 50) / 50.0])


def step(s, a):
    """-> (state', reward, done)"""
    u_leg = jnp.clip(a[0], -1.0, 1.0)
    u_hip = jnp.clip(a[1], -1.0, 1.0)
    leg = jnp.clip(s["leg"] + 2.0 * u_leg * DT, -0.5, 0.5)
    foot = s["z"] - (LEG_REST + leg)
    contact = foot <= 0.0
    push = jnp.maximum(u_leg, 0.0)
    f_ground = jnp.where(contact, -80.0 * foot - 8.0 * s["vz"] + 30.0 * push,
                         0.0)
    vz = s["vz"] + (f_ground - GRAVITY) * DT
    z = jnp.maximum(s["z"] + vz * DT, 0.3)
    pitch = jnp.clip(s["pitch"] + 1.5 * u_hip * DT, -0.8, 0.8)
    vx = s["vx"]
    ax = jnp.where(contact, 12.0 * push * jnp.sin(pitch) - 1.0 * vx,
                   -0.2 * vx)
    vx = jnp.clip(vx + ax * DT, -5.0, 10.0)
    t = s["t"] + 1
    fallen = (z < 0.55) | (jnp.abs(pitch) > 0.75)
    reward = vx + 0.5 - 0.05 * (u_leg ** 2 + u_hip ** 2) - jnp.where(
        fallen, 5.0, 0.0)
    new = {"x": s["x"] + vx * DT, "vx": vx, "z": z, "vz": vz, "leg": leg,
           "pitch": pitch, "t": t}
    return new, reward, fallen | (t >= EPISODE_LEN)
