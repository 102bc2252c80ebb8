"""Hyperparameter adaptation (paper §3.4).

The paper tunes exactly two parallelization hyperparameters, exploiting
that both throughput curves are convex and (nearly) independent:

* **batch size** — GPU-bound: grow geometrically while the *update frame
  rate* (updates/s x batch) keeps improving; stop when the marginal gain
  falls under a threshold (GPU saturated) so the update *frequency* is not
  sacrificed (Table 3: BS32768 row).
* **number of sampling processes** — CPU-bound: grow the vectorized env
  count while the sampling frame rate keeps improving.

We add a third knob the paper's process model doesn't have but the
single-controller mapping does: **rounds per dispatch** — host-bound:
grow the megastep fusion factor while dispatched rounds/s keeps
improving (per-dispatch Python/runtime overhead amortizes, then device
compute dominates and the curve flattens — same convex geometry).

On TPU/CPU-JAX the utilization signal the paper reads from nvidia-smi /
psutil is replaced by the measured steps/s of the compiled functions —
the quantity the utilization was a proxy for.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax


@dataclass
class AdaptLog:
    candidates: List[Dict] = field(default_factory=list)
    chosen: int = 0


def _time_fn(fn: Callable[[], None], iters: int, warmup: int = 1) -> float:
    """Wall seconds per call of ``fn`` (fn must block on its result)."""
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters


def tune_geometric(measure: Callable[[int], float], grid: Sequence[int], *,
                   min_gain: float = 0.10) -> Tuple[int, AdaptLog]:
    """Walk a geometric grid while throughput improves by >= min_gain.

    ``measure(candidate) -> throughput``. Convexity (paper §3.4.2) lets us
    stop at the first sub-threshold step instead of sweeping everything.
    """
    log = AdaptLog()
    best_v, best_thru = grid[0], measure(grid[0])
    log.candidates.append({"value": grid[0], "throughput": best_thru})
    for v in grid[1:]:
        thru = measure(v)
        log.candidates.append({"value": v, "throughput": thru})
        if thru < best_thru * (1.0 + min_gain):
            break                      # convex curve has flattened
        best_v, best_thru = v, thru
    log.chosen = best_v
    return best_v, log


def tune_batch_size(make_update_call: Callable[[int], Callable[[], None]], *,
                    grid: Sequence[int] = (128, 256, 512, 1024, 2048, 4096,
                                           8192, 16384, 32768),
                    iters: int = 5, min_gain: float = 0.10
                    ) -> Tuple[int, AdaptLog]:
    """Pick the batch size maximizing update *frame* rate (Hz x batch)."""

    def measure(bs: int) -> float:
        call = make_update_call(bs)
        sec = _time_fn(call, iters)
        return bs / sec                      # frames/s

    return tune_geometric(measure, grid, min_gain=min_gain)


def tune_num_envs(make_sample_call: Callable[[int], Callable[[], None]], *,
                  grid: Sequence[int] = (1, 2, 4, 8, 16, 32, 64, 128),
                  chunk_len: int = 32, iters: int = 5,
                  min_gain: float = 0.10) -> Tuple[int, AdaptLog]:
    """Pick the env count maximizing sampling frame rate."""

    def measure(n: int) -> float:
        call = make_sample_call(n)
        sec = _time_fn(call, iters)
        return n * chunk_len / sec           # env frames/s

    return tune_geometric(measure, grid, min_gain=min_gain)


def tune_rounds_per_dispatch(make_megastep_call: Callable[[int],
                                                          Callable[[], None]],
                             *, grid: Sequence[int] = (1, 2, 4, 8, 16),
                             iters: int = 5, min_gain: float = 0.10
                             ) -> Tuple[int, AdaptLog]:
    """Pick the megastep fusion factor maximizing dispatched rounds/s."""

    def measure(r: int) -> float:
        call = make_megastep_call(r)
        sec = _time_fn(call, iters)
        return r / sec                       # rounds/s

    return tune_geometric(measure, grid, min_gain=min_gain)


def probe_replay(obs_dim: int, act_dim: int, cap: int, gamma: float, key
                 ):
    """Synthetic filled replay for the update-rate probe, with the SAME
    field set and value domains the trainer feeds the real update graph:
    the trainer always adds a ``"disc"`` row (so the timed HLO must not
    take the ``batch.get("disc", ...)`` fallback path) and ``done`` is a
    {0,1} indicator, not a normal sample."""
    import dataclasses

    import jax.numpy as jnp

    from repro.replay import buffer as rb

    specs = rb.trainer_specs(obs_dim, act_dim)
    fill = {k: jax.random.normal(jax.random.fold_in(key, i),
                                 (cap,) + s).astype(d)
            for i, (k, (s, d)) in enumerate(specs.items())}
    fill["done"] = (fill["done"] > 0).astype(jnp.float32)
    fill["disc"] = gamma * (1.0 - fill["done"])
    replay = rb.init_replay(cap, specs)
    return dataclasses.replace(replay, rows=rb.pack_rows(replay.layout, fill),
                               size=jnp.asarray(cap, jnp.int32))


def auto_tune(env_name: str = "pendulum", algo: str = "sac", *,
              bs_grid: Sequence[int] = (128, 512, 2048, 8192, 32768),
              env_grid: Sequence[int] = (1, 2, 4, 8, 16, 32),
              rpd_grid: Sequence[int] = (1, 2, 4, 8),
              iters: int = 3, mesh=None, placement: str = "ac") -> Dict:
    """End-to-end adaptation for a SpreezeTrainer config (paper's auto mode).

    Returns {"batch_size", "num_envs", "rounds_per_dispatch", "bs_log",
    "env_log", "rpd_log"}. The searches are independent (paper §3.4.2) so
    they run sequentially; the dispatch-fusion search runs last, on a
    trainer probe built with the tuned batch size and env count — and on
    ``mesh``/``placement`` when given, so the fusion factor is tuned on
    the sharded megastep it will actually drive.
    """
    from repro.envs import base as env_base
    from repro.replay import buffer as rb
    from repro.rl.base import AlgoHP, get_algo

    env = env_base.make(env_name)
    spec = env.spec
    mod = get_algo(algo)
    hp = AlgoHP(algo=algo)
    k_init, k_replay, key = jax.random.split(jax.random.PRNGKey(0), 3)
    state = mod.init_state(k_init, spec.obs_dim, spec.act_dim, hp)
    update = mod.make_update_step(hp, spec.obs_dim, spec.act_dim)
    act = mod.make_act(hp)

    cap = max(bs_grid) * 2
    replay = probe_replay(spec.obs_dim, spec.act_dim, cap, hp.gamma,
                          k_replay)

    def make_update_call(bs: int):
        step = jax.jit(lambda s, k: update(
            s, rb.sample(replay, k, bs), jax.random.fold_in(k, 1)))
        holder = {"s": state, "k": key}

        def call():
            holder["s"], m = step(holder["s"], holder["k"])
            holder["k"] = jax.random.fold_in(holder["k"], 2)
            jax.block_until_ready(m["critic_loss"])
        return call

    chunk_len = 32

    def make_sample_call(n: int):
        states = env.reset_batch(jax.random.fold_in(key, n), n)

        def chunk(actor, states, k):
            def step(carry, _):
                st, k = carry
                k, ka, kr = jax.random.split(k, 3)
                obs = jax.vmap(env.observe)(st)
                a = act(actor, obs, ka)
                st, _, rew, _ = jax.vmap(env.autoreset_step)(
                    st, a, jax.random.split(kr, n))
                return (st, k), rew.mean()
            (st, k), r = jax.lax.scan(step, (states, k), None,
                                      length=chunk_len)
            return st, r.mean()

        step = jax.jit(chunk)
        holder = {"st": states, "k": key}

        def call():
            holder["st"], r = step(state.actor, holder["st"], holder["k"])
            holder["k"] = jax.random.fold_in(holder["k"], 3)
            jax.block_until_ready(r)
        return call

    bs, bs_log = tune_batch_size(make_update_call, grid=bs_grid, iters=iters)
    ne, env_log = tune_num_envs(make_sample_call, grid=env_grid,
                                chunk_len=chunk_len, iters=iters)

    # third knob: megastep fusion factor, probed on a real trainer built
    # with the two tuned values (deferred import: pipeline imports us)
    from repro.core.pipeline import SpreezeConfig, SpreezeTrainer

    def make_megastep_call(r: int):
        cfg = SpreezeConfig(env_name=env_name, algo=algo, num_envs=ne,
                            batch_size=bs, chunk_len=chunk_len,
                            replay_capacity=max(2 * bs, 4096),
                            warmup_frames=0, eval_every_rounds=10**9,
                            rounds_per_dispatch=r,
                            mesh=mesh, placement=placement)
        tr = SpreezeTrainer(cfg)

        def call():
            (tr.state, tr.replay, tr.env_states, tr.key, m) = tr._megastep(
                tr.state, tr.replay, tr.env_states, tr.key)
            jax.block_until_ready(m["critic_loss"])
        return call

    rpd, rpd_log = tune_rounds_per_dispatch(make_megastep_call,
                                            grid=rpd_grid, iters=iters)
    return {"batch_size": bs, "num_envs": ne, "rounds_per_dispatch": rpd,
            "bs_log": bs_log, "env_log": env_log, "rpd_log": rpd_log}
