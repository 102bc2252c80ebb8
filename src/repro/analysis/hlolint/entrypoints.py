"""Builders: representative (jitted fn, args) pairs per contract name.

Contracts are *declared* next to their jit sites (``HLOLINT_CONTRACTS``
in the modules listed in ``CONTRACT_MODULES``); this module knows how to
*instantiate* each one — construct a probe-sized trainer/model, hand the
harness the fresh jitted callable plus example args, the symbol table
for the contract's dim expressions, and a ``drive(n)`` protocol that
performs representative dispatches (threading donated outputs back as
inputs) for the retrace check.

Probe sizes mirror ``benchmarks/roofline.py --megastep`` for the
sharded arms (cap 4096, batch 64 on the ac2 x batch4 mesh, Pallas on)
so the contract checked in CI is the artifact the roofline measures.
"""
from __future__ import annotations

import importlib
from typing import Callable, Dict, List

#: modules that may declare module-level HLOLINT_CONTRACTS tuples
CONTRACT_MODULES = (
    "repro.core.pipeline",
    "repro.core.faults",
    "repro.kernels.ops",
    "repro.train.trainer",
    "repro.serve.engine",
    "repro.replay.buffer",
)


def collect_contracts() -> List:
    out = []
    for name in CONTRACT_MODULES:
        mod = importlib.import_module(name)
        out.extend(getattr(mod, "HLOLINT_CONTRACTS", ()))
    return out


# --------------------------------------------------------------------------- #
# spreeze trainer entrypoints
# --------------------------------------------------------------------------- #

def _spreeze_trainer(*, mesh=None, prioritized=False, capacity=2048,
                     batch=32, pallas=False):
    from repro.core import SpreezeConfig, SpreezeTrainer
    cfg = SpreezeConfig(
        env_name="pendulum", algo="sac", num_envs=2, batch_size=batch,
        chunk_len=4, updates_per_round=2, rounds_per_dispatch=2,
        warmup_frames=64, replay_capacity=capacity,
        eval_every_rounds=10**9, mesh=mesh, use_pallas=pallas,
        prioritized=prioritized, seed=3)
    return SpreezeTrainer(cfg)


def _megastep(*, sharded: bool = False, prioritized: bool = False):
    def build() -> Dict:
        import jax
        mesh, groups = None, 1
        capacity, batch = 2048, 32
        if sharded:
            from repro.launch.mesh import make_ac_mesh
            mesh = make_ac_mesh(2, 4)
            groups = mesh.shape["batch"]
            capacity, batch = 4096, 64      # the roofline's probe sizes
        tr = _spreeze_trainer(mesh=mesh, prioritized=prioritized,
                              capacity=capacity, batch=batch,
                              pallas=sharded)
        args = (tr.state, tr.replay, tr.env_states, tr.key)
        live = {"args": args}

        def drive(n: int) -> None:
            s, r, e, k = live["args"]
            for _ in range(n):
                s, r, e, k, _metrics = tr._megastep(s, r, e, k)
            live["args"] = (s, r, e, k)

        return {"fn": tr._megastep, "args": args,
                "params": {"capacity": capacity, "batch": batch,
                           "groups": groups, "k": batch},
                "donated_leaves": len(jax.tree.leaves(args[:3])),
                "drive": drive}
    return build


def _sampler_chunk():
    import jax
    tr = _spreeze_trainer()
    live = {"env": tr.env_states, "key": tr.key}

    def drive(n: int) -> None:
        for _ in range(n):
            e, _flat, k, _rew = tr._sampler(tr.state.actor, live["env"],
                                            live["key"])
            live["env"], live["key"] = e, k

    return {"fn": tr._sampler,
            "args": (tr.state.actor, tr.env_states, tr.key),
            "params": {},
            "donated_leaves": len(jax.tree.leaves(tr.env_states)),
            "drive": drive}


def _update_round():
    import jax
    tr = _spreeze_trainer()
    live = {"args": (tr.state, tr.replay, tr.key)}

    def drive(n: int) -> None:
        s, r, k = live["args"]
        for _ in range(n):
            s, r, k, _loss = tr._update_round(s, r, k)
        live["args"] = (s, r, k)

    return {"fn": tr._update_round, "args": live["args"],
            "params": {},
            "donated_leaves": len(jax.tree.leaves((tr.state, tr.replay))),
            "drive": drive}


# --------------------------------------------------------------------------- #
# replay ring
# --------------------------------------------------------------------------- #

def _replay_add_batch():
    import jax
    import jax.numpy as jnp
    from repro.replay import buffer

    state = buffer.init_replay(256, buffer.specs_for_env(3, 1))
    batch = {k: jnp.ones((8,) + shape, jnp.float32)
             for k, shape in state.layout}
    # a FRESH keyed-jit wrapper: the module-level lru cache may already
    # hold traces from earlier work in this process, which would
    # pollute the retrace probe
    fn = buffer._pallas_keyed_jit(buffer.add_batch)(
        buffer._ring_trace_key())
    live = {"state": state}

    def drive(n: int) -> None:
        for _ in range(n):
            live["state"] = fn(live["state"], batch)

    return {"fn": fn, "args": (state, batch), "params": {},
            "donated_leaves": len(jax.tree.leaves(state)),
            "drive": drive}


# --------------------------------------------------------------------------- #
# kernels/ops sharded replay wrappers (standalone, on the trainer mesh)
# --------------------------------------------------------------------------- #

def _ops_rules():
    from repro.distributed.sharding import trainer_rules
    from repro.launch.mesh import make_ac_mesh
    return trainer_rules(make_ac_mesh(2, 4), "ac")


def _per_topk_sharded():
    import functools

    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops

    rules = _ops_rules()
    cap, k = 1024, 64
    groups = rules.axis_size(rules.batch)
    pri = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (cap,))) + 0.1
    gum = jax.random.gumbel(jax.random.PRNGKey(1), (cap,))
    fn = jax.jit(functools.partial(kops.per_topk_sharded, alpha=0.6, k=k,
                                   rules=rules))
    args = (pri, gum)

    def drive(n: int) -> None:
        for _ in range(n):
            jax.block_until_ready(fn(*args))

    return {"fn": fn, "args": args,
            "params": {"capacity": cap, "k": k, "groups": groups,
                       "batch": k},
            "donated_leaves": 0, "drive": drive}


def _ring_gather_sharded():
    import functools

    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops

    rules = _ops_rules()
    cap, batch = 1024, 64
    groups = rules.axis_size(rules.batch)
    data = jnp.arange(cap * 3, dtype=jnp.float32).reshape(cap, 3)
    idx = jax.random.randint(jax.random.PRNGKey(2), (batch,), 0, cap)
    fn = jax.jit(functools.partial(kops.ring_gather_sharded, rules=rules))
    args = (data, idx)

    def drive(n: int) -> None:
        for _ in range(n):
            jax.block_until_ready(fn(*args))

    return {"fn": fn, "args": args,
            "params": {"capacity": cap, "batch": batch, "groups": groups},
            "donated_leaves": 0, "drive": drive}


# --------------------------------------------------------------------------- #
# resilience layer
# --------------------------------------------------------------------------- #

def _finite_guard():
    import jax
    from repro.core import faults
    from repro.train import resume as resume_lib

    tr = _spreeze_trainer()
    bundle = resume_lib.bundle_from(tr)
    # a FRESH jit: the module-level ``faults.finite_guard`` cache may
    # already hold traces over other structures from earlier work in
    # this process, which would pollute the retrace probe
    fn = jax.jit(faults.tree_finite)

    def drive(n: int) -> None:
        for _ in range(n):
            jax.block_until_ready(fn(bundle))

    return {"fn": fn, "args": (bundle,), "params": {},
            "donated_leaves": 0, "drive": drive}


# --------------------------------------------------------------------------- #
# LM train / serve
# --------------------------------------------------------------------------- #

def _smoke_run_config():
    from repro.configs import ARCHS, get_config
    from repro.configs.base import InputShape, RunConfig
    name = next(a for a in sorted(ARCHS)
                if get_config(a).family == "dense")
    shape = InputShape("hlolint-smoke", seq_len=32, global_batch=2,
                       kind="train")
    return RunConfig(model=get_config(name).reduced(), shape=shape)


def _lm_train_step():
    import jax
    from repro.data.tokens import make_batch
    from repro.train.trainer import init_train_state, make_train_step

    rc = _smoke_run_config()
    k_init, k_batch = jax.random.split(jax.random.PRNGKey(0))
    params, opt_state, opt = init_train_state(rc, k_init)
    batch = make_batch(rc.model, rc.shape, k_batch)
    # hlolint: entrypoint[lm_train_step]
    step_fn = jax.jit(make_train_step(rc, opt), donate_argnums=(0, 1))
    live = {"args": (params, opt_state)}

    def drive(n: int) -> None:
        p, o = live["args"]
        for _ in range(n):
            p, o, _metrics = step_fn(p, o, batch)
        live["args"] = (p, o)

    return {"fn": step_fn, "args": (params, opt_state, batch), "params": {},
            "donated_leaves": len(jax.tree.leaves((params, opt_state))),
            "drive": drive}


def _serve_decode_step():
    import jax
    import jax.numpy as jnp
    from repro.data.tokens import make_batch
    from repro.models import factory
    from repro.serve.engine import _grow_cache, make_decode_step

    rc = _smoke_run_config()
    cfg = rc.model
    k_init, k_batch = jax.random.split(jax.random.PRNGKey(0))
    params = factory.init_params(cfg, k_init)
    batch = make_batch(cfg, rc.shape, k_batch)
    seq = batch["tokens"].shape[1]
    cache, logits = factory.prefill(params, batch, cfg, seq)
    cache = _grow_cache(cfg, cache, seq + 8)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    # hlolint: entrypoint[serve_decode_step]
    decode_fn = jax.jit(make_decode_step(rc), donate_argnums=(2,))
    live = {"cache": cache, "pos": seq}

    def drive(n: int) -> None:
        for i in range(n):
            _lg, c = decode_fn(params, tok, live["cache"],
                               jnp.int32(live["pos"] + i))
            live["cache"] = c

    return {"fn": decode_fn, "args": (params, tok, cache, jnp.int32(seq)),
            "params": {},
            "donated_leaves": len(jax.tree.leaves(cache)),
            "drive": drive}


BUILDERS: Dict[str, Callable[[], Dict]] = {
    "megastep": _megastep(),
    "megastep_per": _megastep(prioritized=True),
    "megastep_sharded": _megastep(sharded=True),
    "megastep_sharded_per": _megastep(sharded=True, prioritized=True),
    "sampler_chunk": _sampler_chunk,
    "update_round": _update_round,
    "replay_add_batch": _replay_add_batch,
    "per_topk_sharded": _per_topk_sharded,
    "ring_gather_sharded": _ring_gather_sharded,
    "finite_guard": _finite_guard,
    "lm_train_step": _lm_train_step,
    "serve_decode_step": _serve_decode_step,
}
