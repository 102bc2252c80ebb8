"""The Spreeze orchestrator: asynchronous sampler / updater / eval pipeline.

Paper (§3.1, Fig. 1/4b): N sampler processes, one network-update process,
one test process and one visualization process run *fully asynchronously*,
exchanging experience through shared RAM and weights through SSD.

TPU/JAX mapping (DESIGN.md §2): a single-controller program where each
"process" is a compiled function and asynchrony comes from JAX async
dispatch — the host enqueues a sampler chunk and K update steps without
blocking on either, so device compute units overlap exactly the way the
paper's processes overlap CPU/GPU. Experience moves through the
device-resident replay ring (shared-memory path) or the host-queue
baseline; weights move to eval either zero-copy ("live") or through
``.npz`` checkpoints ("ssd" — the paper's channel).

The sync-vs-async ablation (Fig. 4a vs 4b) is the ``sync_mode`` flag:
sync blocks on every handoff (centrally-agreed transmission time), async
never blocks except at metric log points.

**Fused megastep** (``rounds_per_dispatch``): the paper's thesis is that
throughput dies at process handoffs, not in compute — and on the
single-controller mapping the handoffs are Python->device dispatches.
The eager loop re-enters Python several times per round (sampler, ring
write, update round, eval/viz gating); with ``rounds_per_dispatch = R``
the trainer instead enqueues ONE compiled ``megastep`` that runs R
iterations of {sampler chunk -> ring write -> K update steps} inside a
``jax.lax.scan`` with all large state donated, and threads the per-round
metrics (mean reward, critic loss) out as stacked (R,) arrays. Tradeoff:
larger R amortizes host dispatch (more rounds/s, the Table 2 quantity)
but coarsens eval/viz gating and weight-sync granularity to R rounds and
lengthens time-to-first-dispatch (compile covers R rounds). The fused
path is only available on the shared-memory transfer in async mode; the
``queue`` baseline and ``sync_mode`` keep the eager per-round loop so
the Fig. 4a ablation (and the dispatch-overhead comparison in
``benchmarks/bench_pipeline.py``) measure exactly what they did before.

**Async host runtime** (``async_eval``, default on): the device side of
the paper's four-process overlap was handled by async dispatch, but the
host side was not — the loop used to run ``float(eval_batch(...))``
inline at every eval window and serialized the ``weight_sync="ssd"``
save/restore into the train thread. Now the loop only *publishes* an
actor snapshot (the ``overlap_eval`` donated copy when available, else
an async device copy) plus the round index into ``core.runtime``'s
latest-wins mailbox and immediately dispatches the next megastep;
background workers fold the round index into the dedicated eval/viz
PRNG streams themselves (publish does zero device dispatch) and run
the jitted eval/viz on their own dispatch streams, the SSD channel's atomic save+restore happens once
per snapshot on its own worker, results land in ``TrainHistory`` in
round order, and solved-early detection arrives through an event the
loop polls. ``sync_mode`` (and ``async_eval=False``) keep the inline
path for the Fig. 4a ablation; ``bench_pipeline --mode eval-overlap``
records the blocked-time gap (Fig. 4b).

**Sharded megastep** (``mesh``/``placement``): with an ("ac", "batch")
jax Mesh the megastep compiles under in/out shardings from
``core.model_parallel`` — the double-Q ensemble axis on ``ac`` (paper
§3.2.2 Fig. 2b: each group updates one Q tower, the only cross-group
traffic is the (B,)-sized ``min(Q1,Q2)`` reduce), the replay ring's
(capacity, ...) leaves on ``batch`` (scatter/gather stay group-local),
the actor replicated. ``placement="dp"`` is the Fig. 2a data-parallel
baseline. ``overlap_eval`` has the megastep emit a donated actor
snapshot each dispatch so the eval/viz "processes" consume weights
without pinning the training state the next dispatch donates.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import functools
import os
import tempfile
import threading
import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlolint.contract import (CollectiveContract,
                                             CollectiveRule,
                                             EntrypointContract)
from repro.core import faults
from repro.core import model_parallel as mp
from repro.core import runtime as rt
from repro.core.transfer import make_transfer
from repro.distributed.sharding import trainer_rules, use_rules
from repro.kernels import ops as kops
from repro.envs import base as env_base
from repro.replay import buffer as rb
from repro.rl.base import AlgoHP, get_algo
from repro.train import checkpoint
from repro.train import resume as resume_lib

# --------------------------------------------------------------------------- #
# hlolint contracts (checked by `python -m repro.analysis.hlolint`)
# --------------------------------------------------------------------------- #
# Machine-readable claims about the COMPILED megastep family — builders
# that instantiate them live in repro.analysis.hlolint.entrypoints.
# Dims are expressions over the probe's symbol table (capacity, batch,
# groups, k == batch for the trainer's PER draw).

#: sharded megastep wire budget, uniform replay: ring-gather
#: reduce-scatters plus grad/param reductions over the ac ensemble.
#: `max_elems="capacity"` is the PR-4 roofline assertion as a standing
#: contract — nothing on the wire may be replay-capacity-sized.
MEGASTEP_COLLECTIVE_CONTRACT = CollectiveContract(
    allow=kops.RING_GATHER_COLLECTIVES + (
        # rank>=2 all-reduces are param-shaped grad/target syncs over
        # the ac axis — structurally unrelated to the replay capacity,
        # so they skip the cap (rank-1 reductions stay capped: a
        # (capacity,) all-reduce would be a PER-globalization bug)
        CollectiveRule("all-reduce", ("*", "*", "..."), cap_exempt=True),
        CollectiveRule("all-reduce", ("*",)),
        # batch-sized index/weight broadcasts between the shard_map ops
        CollectiveRule("all-gather", ("batch",)),
    ),
    max_elems="capacity")

#: PER adds exactly the group-local top-k candidate merge
PER_MEGASTEP_COLLECTIVE_CONTRACT = CollectiveContract(
    allow=MEGASTEP_COLLECTIVE_CONTRACT.allow + kops.PER_TOPK_COLLECTIVES,
    max_elems="capacity")

#: names one stage of the megastep (``sampler_chunk``, ``ring_write``,
#: ``sample``, ``update``, ``reprioritise``) in its ops' ``op_name``
#: metadata, which a device trace carries; the ops themselves do not
#: change. Host spans of the train loop and the workers are
#: ``jax.profiler`` annotations named ``spreeze.*``.
_scope = jax.named_scope

HLOLINT_CONTRACTS = (
    # single-device fused megasteps: donation must fully alias (the
    # replay pool re-materializing every dispatch would double HBM and
    # stall the pipeline), no collectives at all, f32 end to end
    EntrypointContract(name="megastep", module=__name__, donates=True),
    EntrypointContract(name="megastep_per", module=__name__, donates=True),
    # sharded arms: the first dispatch sees freshly-initialized inputs
    # with unconstrained placements; once the megastep's explicitly
    # sharded outputs thread back in, jit commits one more trace and
    # then stays stable — hence 2, not 1 (measured, not slack)
    EntrypointContract(name="megastep_sharded", module=__name__,
                      donates=True, min_devices=8, max_retraces=2,
                      collectives=MEGASTEP_COLLECTIVE_CONTRACT),
    EntrypointContract(name="megastep_sharded_per", module=__name__,
                      donates=True, min_devices=8, max_retraces=2,
                      collectives=PER_MEGASTEP_COLLECTIVE_CONTRACT),
    EntrypointContract(name="sampler_chunk", module=__name__,
                      donates=True),
    EntrypointContract(name="update_round", module=__name__,
                      donates=True),
)


@dataclass
class SpreezeConfig:
    env_name: str = "pendulum"
    algo: str = "sac"
    # parallelization hyperparameters (the two the paper auto-tunes)
    num_envs: int = 16            # "number of sampling processes"
    batch_size: int = 8192
    # pipeline
    replay_capacity: int = 262_144
    warmup_frames: int = 2_048
    chunk_len: int = 32           # env steps fused into one sampler dispatch
    updates_per_round: int = 4    # update steps dispatched per host loop
    rounds_per_dispatch: int = 4  # rounds fused into one device megastep
    fused: Optional[bool] = None  # None = auto (shared transfer + async)
    transfer: str = "shared"      # shared | queue
    queue_size: int = 20_000
    sync_mode: bool = False       # Fig. 4a baseline: block on every handoff
    prioritized: bool = False     # APE-X-style PER on the shared pool
    per_alpha: float = 0.6
    per_beta: float = 0.4
    nstep: int = 1                # n-step returns (APE-X uses 3)
    weight_sync: str = "live"     # live | ssd (paper's channel)
    # multi-device megastep (paper §3.2.2, Fig. 2b/3): an ("ac","batch")
    # jax Mesh — the double-Q ensemble shards over ``ac`` (each group
    # updates one Q tower), the replay ring's rows over ``batch``, the
    # actor replicates. None = the single-device megastep.
    mesh: Optional[Any] = None
    placement: str = "ac"         # ac (Fig. 2b) | dp (Fig. 2a baseline)
    # Pallas replay-ring kernels: None = inherit the ambient
    # ``kernels.ops.use_pallas`` switch at trainer construction. The
    # resolved value is pinned into every trace this trainer compiles
    # (megastep, warmup pushes, eager rounds), so the kernel choice
    # can't drift with the caller's context. With a mesh the kernels
    # run shard_map-native on each group's ring shard.
    use_pallas: Optional[bool] = None
    # megastep emits a donated actor snapshot each dispatch so eval/viz
    # consume weights without pinning the donated training state
    overlap_eval: bool = False
    # eval/vis "processes"
    eval_every_rounds: int = 50   # 0 = off
    eval_episodes: int = 4
    viz_every_rounds: int = 0     # 0 = off; paper's visualization process
    viz_dir: Optional[str] = None  # .npz trajectories land here
    # host-side async runtime (core.runtime): eval/viz/SSD run on worker
    # threads fed by a latest-wins snapshot mailbox, so the train thread
    # never blocks on them. None = auto (async unless sync_mode — the
    # Fig. 4a ablation keeps the inline path).
    async_eval: Optional[bool] = None
    eval_workers: int = 1
    viz_workers: int = 1
    # sanitize mode: run every megastep/round dispatch under
    # jax.transfer_guard("disallow") + jax.debug_nans, turning any
    # host<->device transfer the dispatch path sneaks in (and any NaN a
    # kernel produces) into a hard error. Runtime proof of the
    # device-resident claim tracelint checks statically — CI's
    # forced-8-device job runs a smoke train() with this on.
    sanitize: bool = False
    # resilience layer (docs/robustness.md): supervised workers,
    # preemption-safe full-state snapshots, deterministic fault
    # injection, and rollback on a non-finite megastep carry.
    supervise: bool = True        # retry/degrade workers vs fail fast
    worker_retry_budget: int = 3  # per-consumer crash/hang budget
    worker_heartbeat_s: float = 30.0  # hung-worker watchdog (0 = off)
    snapshot_dir: Optional[str] = None  # None = periodic snapshots off
    snapshot_every_rounds: int = 50     # full-state snapshot cadence
    # wall-clock floor between periodic snapshots: preemption safety
    # bounds lost *time*, so on a fast probe (thousands of rounds/s) a
    # pure round cadence would put the writer in a continuous loop and
    # tax the train thread for durability nobody needs. 0 disables the
    # floor (chaos tests pin snapshots to exact rounds).
    snapshot_min_interval_s: float = 5.0
    keep_snapshots: int = 3             # last-K retention
    max_rollbacks: int = 3        # finite-guard rollback budget
    rollback_lr_backoff: float = 0.5  # lr *= this on every rollback
    fault_plan: Optional[faults.FaultPlan] = None  # injection schedule
    seed: int = 0
    hp: AlgoHP = field(default_factory=AlgoHP)

    def __post_init__(self):
        if self.hp.algo != self.algo:
            self.hp = AlgoHP(**{**self.hp.__dict__, "algo": self.algo})


@dataclass
class TrainHistory:
    """Metrics the paper reports (Tables 2/3, Fig. 5).

    Recording is thread-safe and **round-ordered**: the async runtime's
    eval workers may complete out of publish order, but entries are
    inserted by ``round_i`` so async and inline runs produce the same
    deterministic ordering. The Hz headline metrics count post-warmup
    frames over post-warmup wall time (warmup frames are reported
    separately in ``warmup_frames`` — dividing warmup-inclusive frame
    counts by the post-warmup clock inflated the Table-2 numbers).
    """
    times: List[float] = field(default_factory=list)
    eval_returns: List[float] = field(default_factory=list)
    env_frames: List[int] = field(default_factory=list)
    update_steps: List[int] = field(default_factory=list)
    eval_rounds: List[int] = field(default_factory=list)
    sampling_hz: float = 0.0
    update_hz: float = 0.0            # update frequency (steps/s)
    update_frame_hz: float = 0.0      # update frame rate (steps/s * batch)
    transfer_stats: Dict[str, float] = field(default_factory=dict)
    solved_time: Optional[float] = None
    wall_s: float = 0.0               # timed window (post-warmup wall time)
    warmup_frames: int = 0            # frames sampled during this warmup
    eval_blocked_s: float = 0.0       # train-thread time lost to eval/viz
    runtime_stats: Dict[str, float] = field(default_factory=dict)
    _lock: Any = field(default_factory=threading.Lock, repr=False,
                       compare=False)

    def record_eval(self, t, ret, frames, steps, round_i=None):
        with self._lock:
            if round_i is None:
                round_i = (self.eval_rounds[-1] + 1 if self.eval_rounds
                           else 0)
            i = bisect.bisect_right(self.eval_rounds, round_i)
            self.eval_rounds.insert(i, round_i)
            self.times.insert(i, t)
            self.eval_returns.insert(i, ret)
            self.env_frames.insert(i, frames)
            self.update_steps.insert(i, steps)


def _window_hits(round_i: int, window: int, every: int) -> bool:
    """True iff the round window [round_i, round_i + window) contains a
    multiple of ``every`` — the fused-dispatch generalization of
    ``round_i % every == 0`` (to which it reduces at window == 1)."""
    if not every:
        return False
    return (round_i + window - 1) // every > (round_i - 1) // every


class SpreezeTrainer:
    """End-to-end Spreeze training on a pure-JAX env."""

    def __init__(self, cfg: SpreezeConfig):
        self.cfg = cfg
        self.env = env_base.make(cfg.env_name)
        spec = self.env.spec
        self.algo = get_algo(cfg.algo)
        self.hp = cfg.hp
        self.transfer = make_transfer(cfg.transfer, cfg.queue_size)

        key = jax.random.PRNGKey(cfg.seed)
        self.key, k_algo, k_env, k_io = jax.random.split(key, 4)
        # dedicated eval/viz streams: each consumer folds round_i into its
        # own parent key, so the two never collide with each other (viz at
        # round r used to reuse eval's key from round r+7) or with the
        # live training key
        self._viz_key = jax.random.fold_in(k_io, 0)
        self._eval_key = jax.random.fold_in(k_io, 1)
        self.state = self.algo.init_state(k_algo, spec.obs_dim, spec.act_dim,
                                          self.hp)
        specs = rb.trainer_specs(spec.obs_dim, spec.act_dim)
        if cfg.prioritized:
            from repro.replay import prioritized as per
            if cfg.transfer != "shared":
                raise ValueError("prioritized replay requires the "
                                 "shared-memory transfer path")
            self.replay = per.init_prioritized(cfg.replay_capacity, specs)
            self.transfer = make_transfer("shared",
                                          add_fn=per.add_batch_jit)
        else:
            self.replay = rb.init_replay(cfg.replay_capacity, specs)
        self.env_states = self.env.reset_batch(k_env, cfg.num_envs)

        self.use_pallas = (kops.pallas_enabled() if cfg.use_pallas is None
                           else bool(cfg.use_pallas))
        fusable = cfg.transfer == "shared" and not cfg.sync_mode
        self.use_fused = fusable if cfg.fused is None else cfg.fused
        if self.use_fused and not fusable:
            raise ValueError("fused megastep requires the shared-memory "
                             "transfer path and async mode (sync_mode and "
                             "the queue baseline stay on the eager loop)")
        if cfg.mesh is not None:
            self._check_mesh()
        if cfg.overlap_eval and not self.use_fused:
            raise ValueError("overlap_eval snapshots are emitted by the "
                             "fused megastep; the eager loop's live "
                             "weights already overlap")
        if cfg.async_eval and cfg.sync_mode:
            raise ValueError("async_eval runs eval/viz on background "
                             "workers; sync_mode is the Fig. 4a inline "
                             "ablation — pick one")
        if cfg.eval_workers < 1 or cfg.viz_workers < 1:
            raise ValueError("eval_workers / viz_workers must be >= 1")
        # auto: async host runtime unless the sync ablation asked to block
        self.use_async_eval = ((not cfg.sync_mode) if cfg.async_eval is None
                               else bool(cfg.async_eval))

        self._build_compiled()
        if cfg.mesh is not None:
            # land every carried pytree on its mesh sharding up front so
            # the first megastep donates in place instead of resharding
            self.state = jax.device_put(self.state, self._state_sharding)
            self.replay = jax.device_put(self.replay,
                                         self._replay_sharding)
            self.env_states = jax.device_put(self.env_states,
                                             self._env_sharding)
        self.total_frames = 0
        self.total_updates = 0
        self.last_metrics = None     # stacked (R,) arrays per megastep

    def _check_mesh(self):
        cfg = self.cfg
        if not self.use_fused:
            raise ValueError("the multi-device megastep needs the fused "
                             "path (shared transfer, async mode)")
        names = getattr(cfg.mesh, "axis_names", ())
        if not {"ac", "batch"} <= set(names):
            raise ValueError(f"trainer mesh needs ('ac','batch') axes, "
                             f"got {names}")
        n_q = jax.tree.leaves(self.state.q)[0].shape[0]
        if cfg.placement == "ac" and n_q % cfg.mesh.shape["ac"]:
            raise ValueError(f"ac axis size {cfg.mesh.shape['ac']} must "
                             f"divide the Q ensemble size {n_q} "
                             f"(algo {cfg.algo!r})")
        from repro.launch.mesh import ring_shard_groups
        rows = ring_shard_groups(cfg.mesh, cfg.placement)
        if cfg.replay_capacity % rows:
            raise ValueError(f"replay_capacity {cfg.replay_capacity} must "
                             f"be divisible by the batch-axis size {rows}")
        if self.use_pallas and cfg.batch_size % max(rows, 1):
            # the shard_map gather hands each group batch_size/groups
            # output rows via psum_scatter; an uneven split would
            # silently fall back to the jnp gather, which the Pallas
            # opt-in explicitly forbids
            raise ValueError(f"batch_size {cfg.batch_size} must be "
                             f"divisible by the {rows} ring shards for "
                             f"the mesh-native Pallas ring kernels")
        if (self.use_pallas and cfg.prioritized
                and cfg.batch_size > cfg.replay_capacity // max(rows, 1)):
            # group-local PER: each of the ``rows`` groups emits
            # batch_size top-k candidates from its own ring shard, so
            # the shard must hold at least batch_size rows — otherwise
            # the two-phase select would silently fall back to the
            # global jnp top_k (same opt-in policy as above)
            raise ValueError(
                f"prioritized batch_size {cfg.batch_size} exceeds the "
                f"per-group ring shard "
                f"({cfg.replay_capacity} // {rows} rows) — group-local "
                f"PER selection needs batch_size <= capacity // groups")

    def _rules(self):
        return trainer_rules(self.cfg.mesh, self.cfg.placement)

    # ------------------------------------------------------------------ #
    # compiled "processes"
    # ------------------------------------------------------------------ #
    def _build_compiled(self):
        cfg, env, hp = self.cfg, self.env, self.hp
        act = self.algo.make_act(hp)
        act_det = self.algo.make_act(hp, deterministic=True)
        update = self.algo.make_update_step(hp, env.spec.obs_dim,
                                            env.spec.act_dim)

        def sampler_chunk(actor, states, key):
            """``chunk_len`` vectorized env steps under the live policy.
            Returns (states', experience rows (T*N, ...), key', mean_rew)."""
            def step(carry, _):
                states, key = carry
                key, k_act, k_reset = jax.random.split(key, 3)
                obs = jax.vmap(env.observe)(states)
                a = act(actor, obs, k_act)
                nstates, nobs, rew, done = jax.vmap(env.autoreset_step)(
                    states, a, jax.random.split(k_reset, cfg.num_envs))
                exp = {"obs": obs, "act": a, "rew": rew,
                       "next_obs": nobs, "done": done.astype(jnp.float32)}
                return (nstates, key), exp

            (states, key), exps = jax.lax.scan(
                step, (states, key), None, length=cfg.chunk_len)
            # metric from the RAW per-step rewards: after nstep_chunk the
            # rows carry n-step accumulated returns (~n x inflated)
            mrew = exps["rew"].mean()
            from repro.replay.nstep import nstep_chunk
            exps = nstep_chunk(exps, cfg.nstep, hp.gamma)
            flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in
                    exps.items()}
            return states, flat, key, mrew

        if cfg.prioritized:
            from repro.replay import prioritized as per

            def update_round(state, replay, key):
                """K PER updates: sample -> weighted update -> re-prioritize."""
                def one(carry, _):
                    state, replay, key = carry
                    key, k1, k2 = jax.random.split(key, 3)
                    with _scope("sample"):
                        batch, idx, w = per.sample(
                            replay, k1, cfg.batch_size,
                            alpha=cfg.per_alpha, beta=cfg.per_beta)
                        batch["weight"] = w
                    with _scope("update"):
                        state, metrics = update(state, batch, k2)
                    with _scope("reprioritise"):
                        replay = per.update_priorities(replay, idx,
                                                       metrics["td_abs"])
                    return (state, replay, key), metrics["critic_loss"]

                (state, replay, key), closs = jax.lax.scan(
                    one, (state, replay, key), None,
                    length=cfg.updates_per_round)
                return state, replay, key, closs.mean()
        else:
            def update_round(state, replay, key):
                """K update steps on freshly sampled large batches."""
                def one(carry, _):
                    state, key = carry
                    key, k1, k2 = jax.random.split(key, 3)
                    with _scope("sample"):
                        batch = rb.sample(replay, k1, cfg.batch_size)
                    with _scope("update"):
                        state, metrics = update(state, batch, k2)
                    return (state, key), metrics["critic_loss"]

                (state, key), closs = jax.lax.scan(
                    one, (state, key), None, length=cfg.updates_per_round)
                return state, replay, key, closs.mean()

        def eval_episode(actor, key):
            state0 = env.reset(key)

            def step(carry, _):
                s, total = carry
                a = act_det(actor, env.observe(s), None)
                s, _, r, _ = env.step(s, a)
                return (s, total + r), None

            (s, total), _ = jax.lax.scan(
                step, (state0, jnp.zeros(())), None,
                length=env.spec.episode_len)
            return total

        def eval_batch(actor, key):
            return jax.vmap(lambda k: eval_episode(actor, k))(
                jax.random.split(key, cfg.eval_episodes)).mean()

        def viz_episode(actor, key):
            """Deterministic rollout recording (obs, act, rew) — the
            paper's visualization process, sans GUI: trajectories go to
            .npz for offline rendering."""
            state0 = env.reset(key)

            def step(s, _):
                obs = env.observe(s)
                a = act_det(actor, obs, None)
                s, _, r, _ = env.step(s, a)
                return s, (obs, a, r)

            _, (obs, a, r) = jax.lax.scan(
                step, state0, None, length=env.spec.episode_len)
            return obs, a, r

        if cfg.prioritized:
            from repro.replay import prioritized as per
            push = per.add_batch
        else:
            push = rb.add_batch

        rules = self._rules() if cfg.mesh is not None else None
        pallas_on = self.use_pallas

        def pinned(fn):
            """Pin the trainer's resolved Pallas switch into the trace:
            contexts are read at trace time, and the kernels a trainer
            compiles must not drift with the caller's ambient
            ``use_pallas`` state at whichever call happens to trace."""
            @functools.wraps(fn)
            def wrapped(*a, **kw):
                with kops.use_pallas(pallas_on):
                    return fn(*a, **kw)
            return wrapped

        def make_megastep(rounds: int):
            """One XLA program for ``rounds`` iterations of
            {sampler chunk -> ring write -> K update steps}: the host
            enqueues one dispatch per R rounds instead of ~6 Python->
            device transitions per round. With ``cfg.mesh`` the program
            is built with in/out shardings from ``model_parallel``: the
            double-Q ensemble over ``ac``, the replay rows over
            ``batch``, the actor replicated (paper Fig. 2b)."""

            def megastep(state, replay, env_states, key):
                def one_round(carry, _):
                    state, replay, env_states, key = carry
                    with _scope("sampler_chunk"):
                        env_states, flat, key, mrew = sampler_chunk(
                            state.actor, env_states, key)
                    with _scope("ring_write"):
                        replay = push(replay, flat)
                    state, replay, key, closs = update_round(
                        state, replay, key)
                    return (state, replay, env_states, key), (mrew, closs)

                (state, replay, env_states, key), (rews, closs) = \
                    jax.lax.scan(one_round,
                                 (state, replay, env_states, key),
                                 None, length=rounds)
                metrics = {"mean_rew": rews, "critic_loss": closs}
                # device-side finite guard on the carry: actor params +
                # the stacked round metrics (a NaN anywhere in the Q/env
                # path reaches ``closs``/``rews`` within the same
                # dispatch). Replicated leaves only, so the sharded
                # artifact gains NO collectives; the host polls the
                # result without a sync (jax.Array.is_ready).
                metrics["carry_finite"] = faults.tree_finite(
                    (state.actor, rews, closs))
                if cfg.overlap_eval:
                    # fresh buffers eval can own: the next dispatch then
                    # donates ``state`` without waiting on eval
                    metrics["actor_snapshot"] = jax.tree.map(
                        jnp.copy, state.actor)
                return state, replay, env_states, key, metrics

            if rules is None:
                # hlolint: entrypoint[megastep, megastep_per]
                return jax.jit(pinned(megastep), donate_argnums=(0, 1, 2))

            def sharded_megastep(state, replay, env_states, key):
                # rules + pallas switch active while jit traces: the
                # ring ops dispatch to the shard_map Pallas kernels
                # (each batch group on its local ring shard)
                with use_rules(rules), kops.use_pallas(pallas_on):
                    return megastep(state, replay, env_states, key)

            rep = NamedSharding(cfg.mesh, P())
            metrics_sh = {"mean_rew": rep, "critic_loss": rep,
                          "carry_finite": rep}
            if cfg.overlap_eval:
                metrics_sh["actor_snapshot"] = mp.replicated_sharding(
                    self.state.actor, rules)
            in_sh = (self._state_sharding, self._replay_sharding,
                     self._env_sharding, rep)
            # hlolint: entrypoint[megastep_sharded, megastep_sharded_per]
            return jax.jit(sharded_megastep, donate_argnums=(0, 1, 2),
                           in_shardings=in_sh,
                           out_shardings=in_sh + (metrics_sh,))

        if rules is not None:
            self._state_sharding = mp.algo_state_sharding(self.state, rules)
            self._replay_sharding = mp.replay_sharding(self.replay, rules)
            self._env_sharding = mp.replicated_sharding(self.env_states,
                                                        rules)
        self._viz = jax.jit(viz_episode)
        # hlolint: entrypoint[sampler_chunk]
        self._sampler = jax.jit(pinned(sampler_chunk), donate_argnums=(1,))
        # hlolint: entrypoint[update_round]
        self._update_round = jax.jit(pinned(update_round),
                                     donate_argnums=(0, 1))
        self._eval = jax.jit(eval_batch)
        self._make_megastep = make_megastep
        self._megastep = make_megastep(cfg.rounds_per_dispatch)

    # ------------------------------------------------------------------ #
    # weight sync to the eval/vis "processes"
    # ------------------------------------------------------------------ #
    def _snapshot_actor(self):
        """An actor pytree the eval/viz workers can own: the megastep's
        ``overlap_eval`` donated copy when available, else a fresh
        async-dispatched device copy — either way the next dispatch can
        donate the live training state without pinning it under eval."""
        if (self.cfg.overlap_eval and self.last_metrics is not None
                and "actor_snapshot" in self.last_metrics):
            return self.last_metrics["actor_snapshot"]
        return jax.tree.map(jnp.copy, self.state.actor)

    def _ssd_materialize(self, actor, round_i=None):
        """The paper's SSD weight channel: atomic write-then-rename
        ``.npz``, then read back — consumers never see a torn file."""
        clock = getattr(self, "_fault_clock", None)
        if clock is not None and round_i is not None:
            clock.ssd_oserror(round_i)
        path = getattr(self, "_ssd_path", None)
        if path is None:
            d = tempfile.mkdtemp(prefix="spreeze_ssd_")
            path = self._ssd_path = os.path.join(d, "actor.npz")
        checkpoint.save(path, actor)
        actor, _ = checkpoint.restore(path, actor)
        return actor

    def _actor_for_eval(self, round_i: Optional[int] = None):
        # inline (sync_mode / async_eval=False) weight sync. overlap_eval:
        # the megastep emitted a private actor copy; eval consumes it
        # while the next dispatch donates the live state
        actor = self.state.actor
        if (self.cfg.overlap_eval and self.last_metrics is not None
                and "actor_snapshot" in self.last_metrics):
            actor = self.last_metrics["actor_snapshot"]
        if self.cfg.weight_sync == "live":
            return actor                               # zero-copy
        # SSD path, cached per round: viz and eval landing on the same
        # round share ONE save/restore instead of serializing two full
        # round-trips into the train loop
        cache = getattr(self, "_ssd_cache", None)
        if round_i is not None and cache is not None and \
                cache[0] == round_i:
            return cache[1]
        actor = self._ssd_materialize(actor)
        if round_i is not None:
            self._ssd_cache = (round_i, actor)
        return actor

    # ------------------------------------------------------------------ #
    # the training loop (async by default)
    # ------------------------------------------------------------------ #
    def _warmup(self):
        """Fill the pool with random-policy experience (eager path)."""
        import contextlib
        cfg = self.cfg
        frames_per_chunk = cfg.num_envs * cfg.chunk_len
        # trace the eager ring writes under the trainer rules AND the
        # trainer's pinned Pallas switch, so the warmup pushes dispatch
        # to the same (shard_map-native on a mesh) kernels the megastep
        # compiles — never the single-device kernel on a sharded pool
        rules_ctx = (use_rules(self._rules()) if cfg.mesh is not None
                     else contextlib.nullcontext())
        with rules_ctx, kops.use_pallas(self.use_pallas):
            while self.total_frames < cfg.warmup_frames:
                self.env_states, exp, self.key, _ = self._sampler(
                    self.state.actor, self.env_states, self.key)
                self.replay = self.transfer.push(self.replay, exp)
                self.replay = self.transfer.flush(self.replay)
                self.total_frames += frames_per_chunk
        self.replay = self.transfer.flush(self.replay, force=True)
        if self.cfg.mesh is not None:
            # warmup runs eager jits with inferred shardings; land the
            # carries back on the megastep's exact specs before dispatch
            self.replay = jax.device_put(self.replay,
                                         self._replay_sharding)
            self.env_states = jax.device_put(self.env_states,
                                             self._env_sharding)
        # tracelint: allow[host-transfer] -- warmup barrier: runs once before the timed window opens
        jax.block_until_ready(jax.tree.leaves(self.replay))

    def _viz_dump(self, actor, key, round_i: int) -> None:
        """Run the jitted viz rollout and drop the trajectory to .npz —
        the paper's visualization process, shared by the inline path and
        the async runtime's viz workers."""
        obs, act_tr, rew = self._viz(actor, key)
        if self.cfg.viz_dir:
            import numpy as np
            os.makedirs(self.cfg.viz_dir, exist_ok=True)
            np.savez(os.path.join(self.cfg.viz_dir,
                                  f"traj_{round_i:06d}.npz"),
                     # tracelint: allow[host-transfer] -- viz .npz dump; runs on async viz workers (or the sync ablation)
                     obs=np.asarray(obs), act=np.asarray(act_tr),
                     rew=np.asarray(rew))  # tracelint: allow[host-transfer] -- viz .npz dump (same site as above)

    def _eval_worker_fn(self, actor, round_i):
        """Body of the async eval workers (and the fault-injection
        point for "worker exception"/"worker hang" — the clock fires by
        the snapshot's round index, so failures are reproducible)."""
        clock = getattr(self, "_fault_clock", None)
        if clock is not None:
            clock.eval_fault(round_i)
        # tracelint: allow[host-transfer] -- conversion runs on the async eval worker thread, not the train thread
        return float(self._eval(
            actor, jax.random.fold_in(self._eval_key, round_i)))

    def _make_runtime(self, hist, target_return, log_cb,
                      snapshots: bool = False):
        """The host async runtime for one ``train()`` call: eval/viz/SSD
        (+ full-state snapshot) workers behind latest-wins mailboxes
        (core.runtime), supervised per the config's resilience knobs."""
        cfg = self.cfg
        # workers fold the dedicated eval/viz streams by round index
        # themselves: publishing must stay free of device dispatch (two
        # eager fold_ins on the train thread cost more than the lock)
        return rt.HostRuntime(
            eval_fn=self._eval_worker_fn,
            viz_fn=((lambda actor, round_key, round_i: self._viz_dump(
                actor, jax.random.fold_in(self._viz_key, round_key),
                round_i)) if cfg.viz_every_rounds else None),
            hist=hist,
            materialize_fn=(self._ssd_materialize
                            if cfg.weight_sync == "ssd" else None),
            state_fn=((lambda item: resume_lib.write_bundle(
                cfg.snapshot_dir, item, keep=cfg.keep_snapshots,
                require_finite=True)) if snapshots else None),
            eval_workers=cfg.eval_workers, viz_workers=cfg.viz_workers,
            target_return=target_return, log_cb=log_cb,
            policy=rt.SupervisorPolicy(
                supervise=cfg.supervise,
                max_restarts=cfg.worker_retry_budget,
                heartbeat_timeout_s=cfg.worker_heartbeat_s))

    def _sanitize_scope(self):
        """Guard one hot-loop dispatch when ``cfg.sanitize``:
        ``transfer_guard("disallow")`` turns any host<->device transfer
        into an error and ``debug_nans`` any NaN a step produces. Scoped
        per dispatch so eval/viz/checkpoint (host-side by design) stay
        guard-free."""
        if not self.cfg.sanitize:
            return contextlib.nullcontext()
        # build under a with so a failing enter_context unwinds the
        # already-entered transfer_guard instead of leaking it process-wide;
        # pop_all hands the fully-built stack to the caller's with
        with contextlib.ExitStack() as stack:
            stack.enter_context(jax.transfer_guard("disallow"))
            stack.enter_context(jax.debug_nans(True))
            return stack.pop_all()

    # ------------------------------------------------------------------ #
    # finite-guard polling + rollback (the recovery half of core.faults)
    # ------------------------------------------------------------------ #
    def _poll_guard(self, blocking: bool = False) -> Optional[int]:
        """Oldest round whose ``carry_finite`` metric came back False,
        or None. Non-blocking by default: a flag is only inspected once
        its device buffer is ready (``jax.Array.is_ready``), so the
        poll never syncs the dispatch stream; ``blocking`` drains the
        queue at end of run (the arrays are ready by then anyway)."""
        q = self._guard_q
        while q:
            flag = q[0][1]
            if not blocking:
                ready = getattr(flag, "is_ready", None)
                if ready is not None and not ready():
                    return None
            round_i = q.popleft()[0]
            if not bool(flag):
                return round_i
        return None

    def _rollback(self, runtime, hist, bad_round: int) -> int:
        """Non-finite carry detected: back the LR off, restore the
        latest on-disk snapshot (params, replay, env states, PRNG key,
        counters, history), and hand back the round to resume from.
        Fails loudly (FiniteGuardError) when there is nothing to roll
        back to or the budget is spent — a diverged run must never
        keep training silently."""
        cfg = self.cfg
        self._rollbacks += 1
        if self._rollbacks > cfg.max_rollbacks:
            raise faults.FiniteGuardError(
                f"megastep carry went non-finite at round {bad_round} "
                f"and the rollback budget ({cfg.max_rollbacks}) is spent")
        if runtime is not None:
            # land any in-flight snapshot write / eval result before
            # picking the rollback target (rollback is off the hot path;
            # blocking here is fine)
            runtime.drain()
        path = resume_lib.latest(cfg.snapshot_dir) if cfg.snapshot_dir \
            else None
        if path is None:
            raise faults.FiniteGuardError(
                f"megastep carry went non-finite at round {bad_round} "
                f"and no snapshot exists to roll back to (set "
                f"snapshot_dir to enable rollback)")
        # the LR is baked into the compiled update step (the schedule
        # closes over a Python float), so backing it off means a
        # re-jit — acceptable on this rare, already-blocking path
        self.hp = dataclasses.replace(
            self.hp, lr=self.hp.lr * cfg.rollback_lr_backoff)
        self._build_compiled()
        meta = resume_lib.restore_trainer(self, path)
        resume_lib.hist_restore(hist, meta.get("hist") or {})
        self._guard_q.clear()
        self._ssd_cache = None
        warnings.warn(
            f"non-finite megastep carry at round {bad_round}: rolled "
            f"back to snapshot round {meta['round_i']} with lr backed "
            f"off to {self.hp.lr:g} (rollback {self._rollbacks}/"
            f"{cfg.max_rollbacks})")
        return int(meta["round_i"])  # tracelint: allow[host-transfer] -- plain JSON meta int, not a device value; rollback is off the hot path anyway

    def train(self, *, max_seconds: float = 60.0, max_frames: int = 10**9,
              target_return: Optional[float] = None,
              log_cb: Optional[Callable] = None,
              resume_from: Optional[str] = None) -> TrainHistory:
        cfg = self.cfg
        hist = TrainHistory()
        frames_per_chunk = cfg.num_envs * cfg.chunk_len
        self._fault_clock = (faults.FaultClock(cfg.fault_plan)
                             if cfg.fault_plan is not None else None)
        self._rollbacks = 0
        start_round = 0
        if resume_from is not None:
            # restore BEFORE warmup: the snapshot's frame counter
            # already covers the warmup budget, so _warmup no-ops and
            # the resumed run replays no frames
            meta = resume_lib.restore_trainer(self, resume_from)
            start_round = int(meta["round_i"])  # tracelint: allow[host-transfer] -- plain JSON meta int; restore runs once before the timed window
            resume_lib.hist_restore(hist, meta.get("hist") or {})
        pre_warmup = self.total_frames
        self._warmup()
        # warmup frames counted separately: the Hz headline metrics are
        # post-warmup frames over post-warmup wall time (dividing the
        # warmup-inclusive total by the post-warmup clock inflated them)
        if resume_from is None:
            hist.warmup_frames = self.total_frames - pre_warmup
        frames0, updates0 = self.total_frames, self.total_updates
        # round counters restart every train() call: a same-numbered
        # round from a previous run must not serve its cached SSD actor
        self._ssd_cache = None
        # fused: round counter advances R per dispatch; gating generalizes
        window = cfg.rounds_per_dispatch if self.use_fused else 1
        # pending carry_finite flags, polled without syncing (fused path)
        self._guard_q = collections.deque()
        want_snaps = bool(cfg.snapshot_dir) and cfg.snapshot_every_rounds > 0
        last_snap_t = float("-inf")     # first eligible window snapshots
        runtime = None
        if self.use_async_eval and (cfg.eval_every_rounds
                                    or cfg.viz_every_rounds or want_snaps):
            runtime = self._make_runtime(hist, target_return, log_cb,
                                         snapshots=want_snaps)

        t0 = time.perf_counter()
        round_i = start_round
        solved_at = None
        try:
            while True:
                now = time.perf_counter() - t0
                if now >= max_seconds or self.total_frames >= max_frames:
                    break
                if runtime is not None and runtime.solved.is_set():
                    solved_at = runtime.solved_time
                    break
                # --- finite guard: poll settled flags, roll back on NaN
                with TraceAnnotation("spreeze.guard_poll"):
                    bad_round = self._poll_guard()
                if bad_round is not None:
                    round_i = self._rollback(runtime, hist, bad_round)
                    continue
                clock = self._fault_clock
                if clock is not None and clock.preempt(round_i):
                    # simulated SIGTERM between dispatches: drain the
                    # runtime (every published snapshot is scored, so
                    # the saved history is exact), snapshot, bail out
                    if runtime is not None:
                        runtime.close()
                        hist.runtime_stats = runtime.stats()
                        runtime = None
                    path = (resume_lib.snapshot_now(self, hist, round_i)
                            if cfg.snapshot_dir else None)
                    raise faults.Preempted(
                        f"injected preemption at round {round_i} "
                        f"(snapshot: {path})",
                        snapshot_path=path, round_i=round_i)
                if clock is not None and clock.nan(round_i):
                    self.state = self.state._replace(
                        actor=faults.poison_actor(self.state.actor))
                # one span per dispatch, numbered by its first round
                with StepTraceAnnotation("spreeze.dispatch",
                                         step_num=round_i):
                    if self.use_fused:
                        # --- one device-resident megastep = R rounds -----
                        with self._sanitize_scope():
                            (self.state, self.replay, self.env_states,
                             self.key, self.last_metrics) = self._megastep(
                                self.state, self.replay, self.env_states,
                                self.key)
                        self.total_frames += frames_per_chunk * window
                        self.total_updates += (cfg.updates_per_round
                                               * window)
                        # enqueue the dispatch's finite flag; polled next
                        # iteration once the buffer settles (never syncs)
                        self._guard_q.append(
                            (round_i, self.last_metrics["carry_finite"]))
                    else:
                        # --- sampler "process": dispatch, don't block -----
                        with self._sanitize_scope():
                            self.env_states, exp, self.key, _ = \
                                self._sampler(self.state.actor,
                                              self.env_states, self.key)
                            self.replay = self.transfer.push(self.replay,
                                                             exp)
                        self.total_frames += frames_per_chunk
                        if cfg.sync_mode:
                            jax.block_until_ready(exp)  # Fig. 4a: handoff wait  # tracelint: allow[host-transfer] -- sync_mode ablation measures exactly this stall
                        # --- updater "process" ----------------------------
                        with self._sanitize_scope():
                            self.replay = self.transfer.flush(self.replay)
                            self.state, self.replay, self.key, closs = \
                                self._update_round(self.state, self.replay,
                                                   self.key)
                        self.total_updates += cfg.updates_per_round
                        if cfg.sync_mode:
                            # tracelint: allow[host-transfer] -- sync_mode ablation measures exactly this stall
                            jax.block_until_ready(closs)
                # --- eval / viz "processes" -------------------------------
                want_viz = _window_hits(round_i, window,
                                        cfg.viz_every_rounds)
                want_eval = _window_hits(round_i, window,
                                         cfg.eval_every_rounds)
                if want_viz or want_eval:
                    tb = time.perf_counter()
                    with TraceAnnotation("spreeze.publish"):
                        if runtime is not None:
                            # async: publish the snapshot, keep
                            # dispatching — the workers consume it on their
                            # own streams (eval_key carries the round index;
                            # the workers fold the PRNG streams off-thread)
                            runtime.publish(rt.Snapshot(
                                round_i=round_i,
                                actor=self._snapshot_actor(),
                                eval_key=round_i, viz_key=round_i,
                                t=tb - t0, frames=self.total_frames,
                                steps=self.total_updates,
                                want_eval=want_eval, want_viz=want_viz))
                        else:
                            # inline (sync ablation): block the train thread
                            if want_viz:
                                self._viz_dump(
                                    self._actor_for_eval(round_i),
                                    jax.random.fold_in(self._viz_key,
                                                       round_i),
                                    round_i)
                            if want_eval:
                                # tracelint: allow[host-transfer] -- inline-eval ablation: blocking the train thread is the measured condition
                                ret = float(self._eval(
                                    self._actor_for_eval(round_i),
                                    jax.random.fold_in(self._eval_key,
                                                       round_i)))
                                t = time.perf_counter() - t0
                                hist.record_eval(t, ret,
                                                 self.total_frames,
                                                 self.total_updates,
                                                 round_i=round_i)
                                if log_cb:
                                    log_cb(t, ret, self.total_frames,
                                           self.total_updates)
                                if (target_return is not None
                                        and ret >= target_return
                                        and solved_at is None):
                                    solved_at = t
                                    hist.eval_blocked_s += (
                                        time.perf_counter() - tb)
                                    break
                    hist.eval_blocked_s += time.perf_counter() - tb
                # --- periodic full-state snapshot (preemption safety) -----
                if (want_snaps and _window_hits(round_i, window,
                                                cfg.snapshot_every_rounds)
                        and (time.perf_counter() - last_snap_t
                             >= cfg.snapshot_min_interval_s)):
                    with TraceAnnotation("spreeze.snapshot"):
                        # meta records the NEXT round: everything through
                        # round_i+window-1 is in the bundle, so a resumed
                        # run picks up exactly where this one left off
                        if runtime is not None:
                            # only copy when the writer will pick it up: a
                            # bundle replaced latest-wins still costs a
                            # device dispatch to build
                            if runtime.state_slot_free():
                                runtime.publish_state(
                                    resume_lib.publishable(
                                        self, hist, round_i + window))
                                last_snap_t = time.perf_counter()
                        else:
                            # inline path syncs anyway; vet the bundle so
                            # a poisoned state never becomes a rollback
                            # target
                            resume_lib.write_bundle(
                                cfg.snapshot_dir,
                                resume_lib.publishable(self, hist,
                                                       round_i + window),
                                keep=cfg.keep_snapshots,
                                require_finite=True)
                            last_snap_t = time.perf_counter()
                round_i += window

            with TraceAnnotation("spreeze.barrier"):
                # tracelint: allow[host-transfer] -- end-of-run barrier closing the timed window
                jax.block_until_ready(self.state.step)
                # drain the guard queue: a run whose final dispatches went
                # non-finite must fail loudly, never return as a success
                bad_round = self._poll_guard(blocking=True)
            if bad_round is not None:
                raise faults.FiniteGuardError(
                    f"megastep carry went non-finite at round {bad_round} "
                    f"(detected at end of run)")
            wall = time.perf_counter() - t0
        finally:
            if runtime is not None:
                # graceful drain OUTSIDE the timed window: the last
                # published snapshot is always scored before we return
                runtime.close()
        if runtime is not None:
            if solved_at is None and runtime.solved.is_set():
                solved_at = runtime.solved_time
            hist.runtime_stats = runtime.stats()
        hist.runtime_stats["rollbacks"] = self._rollbacks
        degraded = hist.runtime_stats.get("degraded") or []
        if degraded:
            warnings.warn(
                f"training finished degraded: worker(s) {degraded} "
                f"exhausted their restart budget and were dropped "
                f"(restarts={hist.runtime_stats.get('worker_restarts')}, "
                f"dropped={hist.runtime_stats.get('degraded_dropped')})")
        hist.wall_s = wall
        hist.sampling_hz = (self.total_frames - frames0) / wall
        hist.update_hz = (self.total_updates - updates0) / wall
        hist.update_frame_hz = hist.update_hz * cfg.batch_size
        hist.transfer_stats = self.transfer.stats()
        hist.solved_time = solved_at
        return hist
