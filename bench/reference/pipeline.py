"""Plain reference of one training run: vectorised env rollouts under
the live policy, the n-step fold, a replay ring, uniform or prioritized
sampling, and the algorithm's update, in rounds and dispatches.

It follows the program's data flow from the seed, with its own code:
the same key splits draw the same env resets, policy noise and sample
indices, so the reference and the program see the same experience up
to rounding. Nothing here imports the program. The env, the algorithm
and the networks are found by the names in the configuration file:
``env_<env>.py``, ``algo_<algo>.py`` and ``networks_<networks>.py``
(``networks_mlp.py`` where the file names none) beside this file.
"""
import importlib
import math

import jax
import jax.numpy as jnp


#: the networks of a configuration that names none
DEFAULT_NETWORKS = "mlp"


def networks(cfg):
    return importlib.import_module(
        f"reference.networks_{cfg.get('networks', DEFAULT_NETWORKS)}")


def modules(cfg):
    """-> (env, algorithm, networks) modules of a configuration"""
    return (importlib.import_module(f"reference.env_{cfg['env']}"),
            importlib.import_module(f"reference.algo_{cfg['algo']}"),
            networks(cfg))


def nstep_fold(rew, done, next_obs, n, gamma):
    """(T, N) chunk -> n-step (rew, next_obs, disc); a row stops
    accumulating at an episode end or at the end of the chunk."""
    T = rew.shape[0]
    ret, nxt = rew, next_obs
    disc = gamma * (1.0 - done)
    alive = 1.0 - done
    for i in range(1, min(n, T)):
        later = lambda a: jnp.concatenate(
            [a[i:], jnp.zeros((i,) + a.shape[1:], a.dtype)])
        inside = (jnp.arange(T) + i < T)[:, None]
        take = (alive > 0) & inside
        ret = jnp.where(take, ret + gamma ** i * later(rew), ret)
        nxt = jnp.where(take[..., None], later(next_obs), nxt)
        disc = jnp.where(take, gamma ** (i + 1) * (1.0 - later(done)), disc)
        alive = jnp.where(take, alive * (1.0 - later(done)), 0.0)
    return ret, nxt, disc


#: faults that can be planted in the reference, to put it in the
#: program's place and see the comparison fail
FAULTS = ("half_batch", "skip_reprioritise", "unit_weights",
          "uniform_draws")


def make_run(cfg, mix, dtype=jnp.float32, fault=None):
    """-> (init(key), dispatch(carry) -> (carry, per-round critic losses)).

    Matmuls run at the configuration's ``matmul_precision``. ``fault``
    plants one of ``FAULTS``: each update uses only the first half of
    its sampled batch (its mean taken over that half); or, under
    prioritized replay, the drawn rows keep their priorities, the
    importance weights are all 1, or the rows are drawn uniformly."""
    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    env, algo, net = modules(cfg)
    N, T = mix["num_envs"], mix["chunk_len"]
    B, K, R = mix["batch_size"], mix["updates_per_round"], \
        mix["rounds_per_dispatch"]
    cap, gamma = cfg["replay_capacity"], cfg["gamma"]
    per = mix["prioritized"]

    def chunk(s, rollout, key):
        def one(carry, _):
            (envs, pstate), key = carry
            key, k_act, k_reset = jax.random.split(key, 3)
            obs = jax.vmap(env.observe)(envs)
            a, _, pstate = net.act(s, pstate, obs.astype(dtype), k_act)
            a = a.astype(jnp.float32)

            def env_step(s, a, k):
                s2, r, d = env.step(s, a)
                fresh = env.reset(k)
                s2 = jax.tree.map(lambda x, y: jnp.where(d, y, x), s2, fresh)
                return s2, env.observe(s2), r, d

            envs, nobs, r, d = jax.vmap(env_step)(
                envs, a, jax.random.split(k_reset, N))
            pstate = net.step_end(pstate, d)
            return ((envs, pstate), key), (obs, a, r, nobs,
                                           d.astype(jnp.float32))

        (rollout, key), (obs, act, rew, nobs, done) = jax.lax.scan(
            one, (rollout, key), None, length=T)
        rew, nobs, disc = nstep_fold(rew, done, nobs, mix["nstep"], gamma)
        rows = {"obs": obs, "act": act, "rew": rew, "next_obs": nobs,
                "done": done, "disc": disc}
        rows = {k: v.reshape((N * T,) + v.shape[2:]).astype(dtype)
                for k, v in rows.items()}
        return rollout, rows, key

    def add(ring, rows):
        n = N * T
        slots = (ring["ptr"] + jnp.arange(n)) % cap
        data = {k: ring["data"][k].at[slots].set(rows[k]) for k in rows}
        new = dict(ring, data=data, ptr=(ring["ptr"] + n) % cap,
                   size=jnp.minimum(ring["size"] + n, cap))
        if per:
            new["pri"] = ring["pri"].at[slots].set(ring["max_pri"])
        return new

    def draw(ring, key):
        """-> (row indices, importance weights or None)"""
        if not per or fault == "uniform_draws":
            idx = jax.random.randint(key, (B,), 0,
                                     jnp.maximum(ring["size"], 1))
            idx = (idx + jnp.where(ring["size"] >= cap, ring["ptr"], 0)
                   ) % cap
            return idx, (jnp.ones((B,), jnp.float32) if per else None)
        p = ring["pri"]
        u = jax.random.uniform(key, p.shape, minval=1e-12, maxval=1.0)
        alpha = mix["per_alpha"]
        score = jnp.where(p > 0, alpha * jnp.log(jnp.maximum(p, 1e-12)),
                          -jnp.inf) - jnp.log(-jnp.log(u))
        _, idx = jax.lax.top_k(score, B)
        idx = idx[jnp.arange(B) % jnp.maximum((p > 0).sum(), 1)]
        pa = jnp.maximum(p[idx], 1e-12) ** alpha
        return idx, (pa.min() / pa) ** mix["per_beta"]

    def update_round(s, ring, key):
        def one(carry, _):
            s, ring, key = carry
            key, k_draw, k_upd = jax.random.split(key, 3)
            idx, w = draw(ring, k_draw)
            if fault == "half_batch":
                idx = idx[:B // 2]
                w = None if w is None else w[:B // 2]
            if fault == "unit_weights":
                w = jnp.ones_like(w)
            batch = net.batch(ring["data"], idx, cfg, mix)
            if w is not None:
                batch["weight"] = w.astype(dtype)
            s, closs, td = algo.update(net, s, batch, k_upd, cfg,
                                       cfg["act_dim"])
            if per and fault != "skip_reprioritise":
                new = jnp.abs(td).astype(jnp.float32) + 1e-3
                ring = dict(ring, pri=ring["pri"].at[idx].set(new),
                            max_pri=jnp.maximum(ring["max_pri"], new.max()))
            return (s, ring, key), closs.astype(jnp.float32)

        (s, ring, key), losses = jax.lax.scan(one, (s, ring, key), None,
                                              length=K)
        return s, ring, key, losses.mean()

    def dispatch(carry):
        def one_round(carry, _):
            s, ring, rollout, key = carry
            rollout, rows, key = chunk(s, rollout, key)
            ring = add(ring, rows)
            s, ring, key, loss = update_round(s, ring, key)
            return (s, ring, rollout, key), loss

        return jax.lax.scan(one_round, carry, None, length=R)

    def init(key):
        key, k_algo, k_env, _ = jax.random.split(key, 4)
        s = algo.init(net, k_algo, cfg["obs_dim"], cfg["act_dim"], cfg,
                      dtype)
        # each env's state and its policy's rollout state
        rollout = (jax.vmap(env.reset)(jax.random.split(k_env, N)),
                   net.policy_state(cfg, N, dtype))
        widths = {"obs": cfg["obs_dim"], "act": cfg["act_dim"],
                  "next_obs": cfg["obs_dim"]}
        ring = {"data": {k: jnp.zeros((cap, widths[k]) if k in widths
                                      else (cap,), dtype)
                         for k in ("obs", "act", "rew", "next_obs", "done",
                                   "disc")},
                "ptr": jnp.zeros((), jnp.int32),
                "size": jnp.zeros((), jnp.int32)}
        if per:
            ring["pri"] = jnp.zeros((cap,), jnp.float32)
            ring["max_pri"] = jnp.ones((), jnp.float32)
        # warmup: whole chunks under the initial policy, no updates
        for _ in range(math.ceil(mix["warmup_frames"] / (N * T))):
            rollout, rows, key = chunk(s, rollout, key)
            ring = add(ring, rows)
        return s, ring, rollout, key

    def precise(fn):
        def wrapped(*a):
            with jax.default_matmul_precision(cfg["matmul_precision"]):
                return fn(*a)
        return wrapped

    return precise(jax.jit(init)), \
        precise(jax.jit(dispatch, donate_argnums=0))


def follow(cfg, mix, seed, dispatches=3, dtype=jnp.float32, fault=None):
    """Run the reference through ``dispatches`` dispatches after the
    warmup -> {"losses": each dispatch's per-round critic losses,
    "first_moment"/"second_moment": per-leaf norms of Adam's moments
    after dispatch 1, "params"/"change": per-leaf norms of the trained
    leaves after the last dispatch and of their change since the warmup,
    "priorities": the priority vector after dispatch 1 (None without
    prioritized replay)}. The trained leaves are named by
    ``algo.first_moment``; each leaf is read back in float32 and reduced
    as it comes off the device."""
    import numpy as np

    import compare
    _, algo, net = modules(cfg)
    trained = algo.first_moment(net)
    init, dispatch = make_run(cfg, mix, dtype, fault)
    carry = init(jax.random.PRNGKey(seed))
    fetch = lambda x: np.asarray(x.astype(jnp.float32))
    params0 = compare.host_leaves({k: carry[0][k] for k in trained}, fetch)
    out = {"losses": []}
    for d in range(dispatches):
        carry, losses = dispatch(carry)
        out["losses"].append(np.asarray(losses).tolist())
        if d == 0:
            s = carry[0]
            out["first_moment"] = compare.norms(
                {k: s[opt]["m"] for k, opt in trained.items()}, fetch)
            out["second_moment"] = compare.norms(
                {k: s[opt]["v"] for k, opt in trained.items()}, fetch)
            out["priorities"] = (np.asarray(carry[1]["pri"])
                                 if "pri" in carry[1] else None)
    out["params"], out["change"] = compare.change_norms(
        {k: carry[0][k] for k in trained}, params0, fetch)
    return out
