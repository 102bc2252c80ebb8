import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) pair.

For each pair this proves the sharding config is coherent — the jitted
step lowers, GSPMD partitions it over the production mesh, and the
compiled artifact yields memory/cost/collective numbers for the roofline
(EXPERIMENTS.md §Dry-run / §Roofline). No tensor is ever allocated: all
inputs are ShapeDtypeStructs.

Usage:
  python -m repro.launch.dryrun --arch smollm-360m --shape train_4k
  python -m repro.launch.dryrun --all                  # 40 baseline pairs
  python -m repro.launch.dryrun --all --multipod       # 2-pod mesh
  python -m repro.launch.dryrun --spreeze              # RL AC-parallel step
"""
import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, get_config, get_shape
from repro.configs.base import RunConfig
from repro.distributed.sharding import standard_rules, use_rules
from repro.launch import analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (batch_shardings, cache_shardings,
                                decode_input_specs, input_specs,
                                shape_supported)

REPORT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "reports", "dryrun")


def _run_config(cfg, shape, *, fsdp: bool = True) -> RunConfig:
    # production precision policy: bf16 params + f32 adam moments
    return RunConfig(model=cfg, shape=shape, param_dtype="bfloat16",
                     compute_dtype="bfloat16", fsdp=fsdp)


def _scale_depth(cfg, periods: int):
    """A same-family variant that is ``periods`` scan periods deep."""
    import dataclasses
    if cfg.family == "hybrid":
        return dataclasses.replace(
            cfg, num_layers=periods * cfg.hybrid_attn_every)
    if cfg.family == "encdec":
        return dataclasses.replace(cfg, num_layers=periods,
                                   encoder_layers=periods)
    return dataclasses.replace(cfg, num_layers=periods)


def _n_periods(cfg) -> float:
    if cfg.family == "hybrid":
        return cfg.num_layers / cfg.hybrid_attn_every
    return float(cfg.num_layers)


def _lower_for(rc: RunConfig, rules):
    if rc.shape.kind == "train":
        return _lower_train(rc, rules)
    if rc.shape.kind == "prefill":
        return _lower_prefill(rc, rules)
    return _lower_decode(rc, rules)


def _probe_costs(cfg, shape, rules, periods: int, *,
                 fsdp: bool = True) -> Dict[str, float]:
    """Compile an UNROLLED shallow variant and read exact HLO costs.

    XLA's cost analysis counts a while body once regardless of trip count,
    so the scanned full-depth module undercounts FLOPs by ~L x. The probes
    (1 and 2 periods deep, scans unrolled) give exact per-period costs to
    extrapolate from — including remat recompute and per-layer collectives.
    """
    import dataclasses
    from repro.models.transformer import unroll_scans

    pcfg = _scale_depth(cfg, periods)
    rc = dataclasses.replace(_run_config(pcfg, shape, fsdp=fsdp),
                             model=pcfg)
    with unroll_scans():
        lowered = _lower_for(rc, rules)
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = analysis.collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(coll["total"]),
            "coll_breakdown": {k: float(v) for k, v in coll.items()
                               if k in analysis._COLLECTIVES}}


def _extrapolate(c1: Dict, c2: Dict, n: float) -> Dict[str, float]:
    """outside + n x per-period, from 1- and 2-period probe costs."""
    out = {}
    for k in ("flops", "bytes", "coll"):
        body = c2[k] - c1[k]
        out[k] = max(c1[k] + (n - 1.0) * body, 0.0)
    out["coll_breakdown"] = {
        k: max(c1["coll_breakdown"][k]
               + (n - 1.0) * (c2["coll_breakdown"][k]
                              - c1["coll_breakdown"][k]), 0.0)
        for k in c1["coll_breakdown"]}
    return out


def lower_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
               seq_shard_attn: bool = True, remat: bool = True,
               probes: bool = True, data: int = 16, model: int = 16,
               fsdp: Optional[bool] = None, tag: str = "") -> Dict[str, Any]:
    """Lower + compile one (arch, shape) on the production mesh; returns
    the record for EXPERIMENTS.md (or a skip record).

    §Perf knobs: ``data``/``model`` reshape the intra-pod mesh; ``fsdp``
    False drops the data-axis weight sharding (weights stay TP-resident).
    Default policy (EXPERIMENTS §Perf, h2o long_500k): TP-resident for
    B=1 long-context decode — weight gathers can't amortize over one
    sequence — FSDP everywhere else.
    """
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    if fsdp is None:
        fsdp = shape_name != "long_500k"
    ok, why = shape_supported(cfg, shape)
    mesh_name = (f"2x{data}x{model}" if multi_pod else f"{data}x{model}")
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name}
    if tag:
        rec["variant"] = tag
    if not ok:
        rec["skipped"] = why
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod, data=data, model=model)
    chips = mesh.devices.size
    rules = standard_rules(mesh, sequence_parallel=seq_shard_attn,
                           fsdp=fsdp)
    rc = _run_config(cfg, shape, fsdp=fsdp)
    if not remat:
        import dataclasses
        rc = dataclasses.replace(rc, remat=False)

    t0 = time.perf_counter()
    with use_rules(rules), mesh:
        # 1) full-depth compile: proves lowering; yields peak memory
        lowered = _lower_for(rc, rules)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
        mem = compiled.memory_analysis()

        # 2) unrolled shallow probes: exact per-period HLO costs
        if probes:
            c1 = _probe_costs(cfg, shape, rules, 1, fsdp=fsdp)
            c2 = _probe_costs(cfg, shape, rules, 2, fsdp=fsdp)
            costs = _extrapolate(c1, c2, _n_periods(cfg))
        else:
            cost = compiled.cost_analysis()
            coll = analysis.collective_bytes(compiled.as_text())
            costs = {"flops": float(cost.get("flops", 0.0)),
                     "bytes": float(cost.get("bytes accessed", 0.0)),
                     "coll": float(coll["total"]),
                     "coll_breakdown": {k: float(v) for k, v in coll.items()
                                        if k in analysis._COLLECTIVES}}

    roof = analysis.Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        flops_per_device=costs["flops"],
        bytes_per_device=costs["bytes"],
        collective_bytes_per_device=costs["coll"],
        model_flops=analysis.model_flops_estimate(cfg, shape),
        peak_memory_per_device=float(
            getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            - getattr(mem, "alias_size_in_bytes", 0)),
    ).finalize()

    rec.update(roof.to_dict())
    rec["lower_s"] = round(t_lower, 1)
    rec["compile_s"] = round(t_compile, 1)
    rec["collective_breakdown"] = costs["coll_breakdown"]
    return rec


def _lower_train(rc: RunConfig, rules):
    from repro.models import factory
    from repro.train.optimizer import make_optimizer
    from repro.train.trainer import make_train_step

    cfg = rc.model
    opt = make_optimizer(rc.optimizer, rc.learning_rate,
                         weight_decay=rc.weight_decay, grad_clip=rc.grad_clip)
    step = make_train_step(rc, opt)
    params = jax.eval_shape(
        lambda k: factory.init_params(cfg, k, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(opt.init, params)
    batch = input_specs(cfg, rc.shape)

    from repro.distributed.sharding import params_sharding_tree
    p_sh = params_sharding_tree(params, rules)
    o_sh = params_sharding_tree(opt_state, rules)
    b_sh = batch_shardings(batch, rules)
    # hlolint: exempt -- lowering-only (ShapeDtypeStruct dry-run): never dispatched, no artifact to guard
    return jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                   donate_argnums=(0, 1)).lower(params, opt_state, batch)


def _lower_prefill(rc: RunConfig, rules):
    from repro.models import factory
    from repro.serve.engine import make_prefill_step

    cfg = rc.model
    step = make_prefill_step(rc, rc.shape.seq_len)
    params = jax.eval_shape(
        lambda k: factory.init_params(cfg, k, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    batch = input_specs(cfg, rc.shape)
    from repro.distributed.sharding import params_sharding_tree
    p_sh = params_sharding_tree(params, rules)
    b_sh = batch_shardings(batch, rules)
    return jax.jit(step, in_shardings=(p_sh, b_sh)).lower(params, batch)


def _lower_decode(rc: RunConfig, rules):
    from repro.models import factory
    from repro.serve.engine import make_decode_step

    cfg = rc.model
    step = make_decode_step(rc)
    params = jax.eval_shape(
        lambda k: factory.init_params(cfg, k, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    token, cache, pos = decode_input_specs(cfg, rc.shape)
    from repro.distributed.sharding import params_sharding_tree
    p_sh = params_sharding_tree(params, rules)
    c_sh = cache_shardings(cache, rules)
    t_sh = batch_shardings({"tokens": token}, rules)["tokens"]
    from jax.sharding import NamedSharding, PartitionSpec as P
    pos_sh = NamedSharding(rules.mesh, P())
    # hlolint: exempt -- lowering-only (ShapeDtypeStruct dry-run): never dispatched, no artifact to guard
    return jax.jit(step, in_shardings=(p_sh, t_sh, c_sh, pos_sh),
                   donate_argnums=(2,)).lower(params, token, cache, pos)


# ---------------------------------------------------------------------------
# Spreeze RL AC-parallel dry-run (the paper's technique at pod scale)
# ---------------------------------------------------------------------------

def lower_spreeze(*, multi_pod: bool = True, algo: str = "sac",
                  batch_size: int = 8192,
                  placement: str = "ac") -> Dict[str, Any]:
    """Lower the RL update on the production mesh. placement="ac" is the
    paper's Fig. 2b (critics over the pod axis); "dp" is the Fig. 2a
    data-parallel baseline (gradient all-reduce across pods)."""
    from repro.core.model_parallel import make_spreeze_update

    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    with mesh:
        update_fn, state, batch, key, in_sh = make_spreeze_update(
            mesh, algo=algo, batch_size=batch_size, placement=placement)
        # hlolint: exempt -- lowering-only 512-device dry-run; never dispatched
        lowered = jax.jit(update_fn, in_shardings=in_sh,
                          donate_argnums=(0,)).lower(state, batch, key)
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = analysis.collective_bytes(compiled.as_text())
    return {"mode": "spreeze_rl_update", "algo": algo, "mesh": mesh_name,
            "batch_size": batch_size, "placement": placement,
            "flops_per_device": float(cost.get("flops", 0.0)),
            "bytes_per_device": float(cost.get("bytes accessed", 0.0)),
            "collective_bytes_per_device": float(coll["total"]),
            "collective_count": int(coll["count"]),
            "collective_breakdown": {k: v for k, v in coll.items()
                                     if k in analysis._COLLECTIVES}}


def lower_spreeze_arch(arch: str, *, batch: int = 32, seq: int = 1024,
                       act_dim: int = 16) -> Dict[str, Any]:
    """RLHF-scale Spreeze: an assigned architecture as the actor/critic
    backbone, actor tower on pod 0's groups, double-Q critic towers
    sharded over the pod (=ac) axis — the paper's Fig. 3 with LLMs.

    Lowers one combined update step (critic grads + actor grads) on the
    2-pod mesh and reports the roofline inputs.
    """
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.model_parallel import make_arch_spreeze_losses
    from repro.distributed.sharding import (params_sharding_tree,
                                            spreeze_rules, use_rules)
    from repro.rl import networks as nets

    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=True)
    rules = spreeze_rules(mesh)
    actor_loss, critic_loss = make_arch_spreeze_losses(cfg, act_dim)

    with use_rules(rules), mesh:
        actor = jax.eval_shape(
            lambda k: nets.init_arch_policy(k, cfg, act_dim,
                                            dtype=jnp.bfloat16),
            jax.random.PRNGKey(0))
        critic1 = jax.eval_shape(
            lambda k: nets.init_arch_q(k, cfg, act_dim,
                                       dtype=jnp.bfloat16),
            jax.random.PRNGKey(1))
        critics = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((2,) + l.shape, l.dtype),
            critic1)

        a_sh = params_sharding_tree(actor, rules)
        # critic ensemble: pod axis on dim 0, then the per-tower 2-D
        # param sharding shifted right by one dim
        per = params_sharding_tree(critic1, rules)
        c_sh = jax.tree.map(
            lambda s, l: NamedSharding(mesh, P("pod", *s.spec)),
            per, critics)

        tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        act = jax.ShapeDtypeStruct((batch, act_dim), jnp.float32)
        rew = jax.ShapeDtypeStruct((batch,), jnp.float32)
        done = jax.ShapeDtypeStruct((batch,), jnp.float32)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        t_sh = NamedSharding(mesh, P("data", None))
        v_sh = NamedSharding(mesh, P("data"))
        rep = NamedSharding(mesh, P())

        def update(actor, critics, critics_tgt, tokens, act, rew, done,
                   key):
            with use_rules(rules):
                cg = jax.grad(critic_loss)(critics, critics_tgt, actor,
                                           tokens, act, rew, done, key)
                ag = jax.grad(actor_loss)(actor, critics, tokens, key)
            return cg, ag

        lowered = jax.jit(update, in_shardings=(
            a_sh, c_sh, c_sh, t_sh, NamedSharding(mesh, P("data", None)),
            v_sh, v_sh, rep)).lower(actor, critics, critics, tokens, act,
                                    rew, done, key)
        compiled = lowered.compile()

    cost = compiled.cost_analysis()
    coll = analysis.collective_bytes(compiled.as_text())
    mem = compiled.memory_analysis()
    return {"mode": "spreeze_arch_update", "arch": arch, "mesh": "2x16x16",
            "batch": batch, "seq": seq,
            "flops_per_device": float(cost.get("flops", 0.0)),
            "bytes_per_device": float(cost.get("bytes accessed", 0.0)),
            "collective_bytes_per_device": float(coll["total"]),
            "collective_breakdown": {k: v for k, v in coll.items()
                                     if k in analysis._COLLECTIVES},
            "peak_memory_per_device": float(
                getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0))}


def lower_spreeze_sampler(*, env_name: str = "pendulum",
                          num_envs: int = 4096, chunk_len: int = 32
                          ) -> Dict[str, Any]:
    """Pod-scale experience sampling: the paper's N sampler processes
    become ``num_envs`` vmapped env instances sharded over (pod, data) —
    each device group steps its own env shard under the replicated actor
    with zero cross-device traffic inside the chunk.
    """
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.envs import base as env_base
    from repro.rl.base import AlgoHP, get_algo

    env = env_base.make(env_name)
    hp = AlgoHP(algo="sac")
    mod = get_algo("sac")
    act = mod.make_act(hp)
    mesh = make_production_mesh(multi_pod=True)

    with mesh:
        actor = jax.eval_shape(
            lambda k: mod.init_state(k, env.spec.obs_dim, env.spec.act_dim,
                                     hp).actor, jax.random.PRNGKey(0))
        states = jax.eval_shape(
            lambda k: env.reset_batch(k, num_envs), jax.random.PRNGKey(1))
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)

        def chunk(actor, states, key):
            def step(carry, _):
                st, k = carry
                k, ka, kr = jax.random.split(k, 3)
                obs = jax.vmap(env.observe)(st)
                a = act(actor, obs, ka)
                st, nobs, rew, done = jax.vmap(env.autoreset_step)(
                    st, a, jax.random.split(kr, num_envs))
                exp = {"obs": obs, "act": a, "rew": rew, "next_obs": nobs,
                       "done": done.astype(jnp.float32)}
                return (st, k), exp
            (st, k), exps = jax.lax.scan(step, (states, key), None,
                                         length=chunk_len)
            return st, exps

        rep = jax.tree.map(lambda l: NamedSharding(mesh, P()), actor)
        st_sh = jax.tree.map(
            lambda l: NamedSharding(
                mesh, P(("pod", "data"), *([None] * (l.ndim - 1)))),
            states)
        compiled = jax.jit(chunk, in_shardings=(
            rep, st_sh, NamedSharding(mesh, P()))).lower(
                actor, states, key).compile()

    coll = analysis.collective_bytes(compiled.as_text())
    cost = compiled.cost_analysis()
    return {"mode": "spreeze_sampler", "env": env_name,
            "num_envs": num_envs, "chunk_len": chunk_len, "mesh": "2x16x16",
            "flops_per_device": float(cost.get("flops", 0.0)),
            "collective_bytes_per_device": float(coll["total"]),
            "collective_count": int(coll["count"])}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--spreeze", action="store_true")
    ap.add_argument("--spreeze-batch", type=int, default=8192)
    ap.add_argument("--spreeze-arch", default=None, metavar="ARCH",
                    help="lower the RLHF-scale AC update with this "
                         "assigned arch as actor/critic backbone")
    ap.add_argument("--spreeze-sampler", action="store_true",
                    help="lower the pod-scale vmapped env sampler chunk")
    ap.add_argument("--no-seq-shard", action="store_true",
                    help="disable sequence(context) parallel attention")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true",
                    help="TP-resident weights (decode optimization)")
    ap.add_argument("--data", type=int, default=16,
                    help="intra-pod data-axis size (data*model == 256)")
    ap.add_argument("--model", type=int, default=16)
    ap.add_argument("--tag", default="",
                    help="variant label; JSON written as <pair>_<tag>.json")
    ap.add_argument("--out", default=REPORT_DIR)
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)

    if args.spreeze_arch:
        rec = lower_spreeze_arch(args.spreeze_arch)
        print(json.dumps(rec, indent=2))
        with open(os.path.join(
                args.out, f"spreeze_arch_{args.spreeze_arch}.json"),
                "w") as f:
            json.dump(rec, f, indent=2)
        return 0

    if args.spreeze_sampler:
        rec = lower_spreeze_sampler()
        print(json.dumps(rec, indent=2))
        with open(os.path.join(args.out, "spreeze_sampler.json"), "w") as f:
            json.dump(rec, f, indent=2)
        return 0

    if args.spreeze:
        for placement in ("ac", "dp"):
            rec = lower_spreeze(multi_pod=True, placement=placement,
                                batch_size=args.spreeze_batch)
            print(json.dumps(rec, indent=2))
            with open(os.path.join(args.out,
                                   f"spreeze_rl_{placement}.json"),
                      "w") as f:
                json.dump(rec, f, indent=2)
        return 0

    pairs = []
    if args.all:
        pairs = [(a, s) for a in sorted(ARCHS) for s in
                 ("train_4k", "prefill_32k", "decode_32k", "long_500k")]
    elif args.arch and args.shape:
        pairs = [(args.arch, args.shape)]
    else:
        ap.error("--arch+--shape or --all or --spreeze required")

    failures = 0
    for arch, shape in pairs:
        mesh_name = (f"2x{args.data}x{args.model}" if args.multipod
                     else f"{args.data}x{args.model}")
        tag = f"{arch}_{shape}_{mesh_name}"
        if args.tag:
            tag += f"_{args.tag}"
        try:
            rec = lower_pair(arch, shape, multi_pod=args.multipod,
                             seq_shard_attn=not args.no_seq_shard,
                             remat=not args.no_remat,
                             data=args.data, model=args.model,
                             fsdp=False if args.no_fsdp else None,
                             tag=args.tag)
            status = ("SKIP: " + rec["skipped"]) if "skipped" in rec else (
                f"ok  compute={rec['compute_s']:.3e}s "
                f"memory={rec['memory_s']:.3e}s "
                f"coll={rec['collective_s']:.3e}s "
                f"bottleneck={rec['bottleneck']} "
                f"mem/dev={rec['peak_memory_per_device']/2**30:.2f}GiB "
                f"compile={rec['compile_s']}s")
            print(f"[{tag}] {status}", flush=True)
        except Exception as e:
            failures += 1
            rec = {"arch": arch, "shape": shape, "error": str(e),
                   "traceback": traceback.format_exc()}
            print(f"[{tag}] FAIL {e}", flush=True)
        with open(os.path.join(args.out, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=2)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
