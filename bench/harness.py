"""Runs one benchmark cell once: set-up, a measured window, the check.

Set-up: JAX start-up; the trainer built from the seed (parameters and
env states made on the device); the replay warmup; the megastep's
first dispatches, through ``SpreezeTrainer.train`` as the window calls
it, until the ring has wrapped once. The first three of those
dispatches are the ones the reference follows. The window: one
``train(max_seconds=...)`` call in steady state, timed on this
module's own clock and ending in ``block_until_ready``. After it the
program's state is freed and the plain reference runs.
"""
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time

import compare
from catalog import Catalog

#: dispatches the reference follows from the seed
FOLLOWED = 3
#: runtime counters that count as failures
FAULTS = ("worker_restarts", "worker_hangs", "rollbacks")


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


#: configuration and mix keys that only the reference or the documents
#: read; every other key names a field of SpreezeConfig or AlgoHP
REFERENCE_ONLY = ("obs_dim", "act_dim", "episode_len", "dtype",
                  "matmul_precision", "source", "description", "assumed",
                  "deployment", "networks")
#: keys that name a field by another name
RENAMED = {"env": "env_name"}
#: fields the harness sets itself, never a configuration
HARNESS_SET = ("seed", "use_pallas", "hp")


def _value(v):
    return tuple(_value(x) for x in v) if isinstance(v, list) else v


def program_config(cfg, mix, seed):
    """The program's configuration of a cell, by field name: each key of
    the configuration and of the mix goes to the field of that name of
    SpreezeConfig, of AlgoHP, or of both (``env`` goes to ``env_name``),
    JSON lists as tuples. The keys in REFERENCE_ONLY stay out; any other
    key, or one of them that both files set, is an error that names it."""
    import dataclasses

    from repro.core import SpreezeConfig
    from repro.rl.base import AlgoHP
    names = lambda cls: {f.name for f in dataclasses.fields(cls)}
    top, hp_names = names(SpreezeConfig) - set(HARNESS_SET), names(AlgoHP)
    top_kw, hp_kw, seen = {"seed": seed, "use_pallas": True}, {}, {}
    for where, keys in (("configuration", cfg), ("traffic mix", mix)):
        for key, value in keys.items():
            if key in REFERENCE_ONLY:
                continue
            if key in seen:
                raise ValueError(f"key {key!r} is set by both the "
                                 f"{seen[key]} and the {where}")
            seen[key] = where
            name = RENAMED.get(key, key)
            if key in HARNESS_SET or key in RENAMED.values() or (
                    name not in top and name not in hp_names):
                raise ValueError(
                    f"{where} key {key!r} names no field of SpreezeConfig "
                    f"or AlgoHP that a configuration may set")
            for fields, kw in ((top, top_kw), (hp_names, hp_kw)):
                if name in fields:
                    kw[name] = _value(value)
    return SpreezeConfig(**top_kw, hp=AlgoHP(**hp_kw))


def _layout(tree):
    """A program tree in the reference's layout: a dict whose keys are
    ``l0`` .. ``l<n-1>`` becomes the list of its values; other dicts keep
    their keys."""
    if not isinstance(tree, dict):
        return tree
    if tree and set(tree) == {f"l{i}" for i in range(len(tree))}:
        return [_layout(tree[f"l{i}"]) for i in range(len(tree))]
    return {k: _layout(v) for k, v in tree.items()}


def trained_fields(state):
    """{trained field: its optimizer's field} of the program's state, by
    one rule: a field ``opt_X`` holds the optimizer state of field ``X``,
    or of ``log_X`` where the state has no ``X``. The reference names
    the same leaves by the same field names."""
    fields = state._fields
    out = {}
    for f in fields:
        if f.startswith("opt_"):
            x = f[len("opt_"):]
            t = x if x in fields else f"log_{x}"
            if t not in fields:
                raise ValueError(f"state field {f!r} has no field {x!r} "
                                 f"or {t!r} to train")
            out[t] = f
    return out


def trained(state):
    """The program's trained leaves, on the device, in the reference's
    layout."""
    return {t: _layout(getattr(state, t)) for t in trained_fields(state)}


def moments(state):
    """Adam's first and second moments of the trained leaves, on the
    device, in the reference's layout."""
    opts = {t: getattr(state, f) for t, f in trained_fields(state).items()}
    return ({t: _layout(o.mu) for t, o in opts.items()},
            {t: _layout(o.nu) for t, o in opts.items()})


def follow_program(tr, dispatches):
    """Warmup and ``dispatches`` dispatches, each one ``train()`` call
    -> the record ``compare.readings`` takes: per-leaf norms, each leaf
    reduced as it comes off the device."""
    import numpy as np
    per_dispatch = tr.cfg.num_envs * tr.cfg.chunk_len * \
        tr.cfg.rounds_per_dispatch
    tr.train(max_frames=1)                        # the replay warmup
    params0 = compare.host_leaves(trained(tr.state))
    out = {"losses": []}
    for d in range(dispatches):
        tr.train(max_frames=tr.total_frames + per_dispatch)
        out["losses"].append(np.asarray(
            tr.last_metrics["critic_loss"]).tolist())
        if d == 0:
            first, second = moments(tr.state)
            out["first_moment"] = compare.norms(first)
            out["second_moment"] = compare.norms(second)
            pri = getattr(tr.replay, "priorities", None)
            out["priorities"] = None if pri is None else np.asarray(pri)
    out["params"], out["change"] = compare.change_norms(trained(tr.state),
                                                        params0)
    return out


def fill_dispatches(cfg, mix):
    """Dispatches after the warmup until the ring has wrapped once."""
    warm = mix["num_envs"] * mix["chunk_len"] * math.ceil(
        mix["warmup_frames"] / (mix["num_envs"] * mix["chunk_len"]))
    per = mix["num_envs"] * mix["chunk_len"] * mix["rounds_per_dispatch"]
    return max(FOLLOWED, math.ceil((cfg["replay_capacity"] - warm) / per))


def traced_dispatches(mix):
    """The traced window: two eval periods, short enough to read back,
    long enough to hold evals in their steady-state proportion."""
    return max(1, math.ceil(2 * mix["eval_every_rounds"]
                            / mix["rounds_per_dispatch"]))


def peak_bytes(devices):
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


class CompileCounter:
    """Counts XLA compilations (persistent-cache misses) while on."""

    def __init__(self):
        import jax
        self.n, self.on = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def run_cell(cat, cell, cfg, mix, limits, seed, seconds, trace, devices,
             t_start):
    """Set-up, window and check of one cell -> the result dict."""
    import jax

    from repro.core import SpreezeTrainer
    from reference import pipeline

    compiles = CompileCounter()
    phases = [("start-up", time.perf_counter())]
    tr = SpreezeTrainer(program_config(cfg, mix, seed))
    phases.append(("trainer built", time.perf_counter()))
    per_dispatch = mix["num_envs"] * mix["chunk_len"] * \
        mix["rounds_per_dispatch"]
    prog = follow_program(tr, FOLLOWED)
    phases.append(("warmup and followed dispatches", time.perf_counter()))
    tr.train(max_frames=tr.total_frames + per_dispatch * (
        fill_dispatches(cfg, mix) - FOLLOWED))
    jax.block_until_ready(tr.state)
    phases.append(("ring filled", time.perf_counter()))
    setup_s = time.perf_counter() - t_start
    log("set-up: " + "; ".join(
        f"{name} {t - t0:.3f} s" for (name, t), (_, t0) in
        zip(phases, [("", t_start)] + phases[:-1])))

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    compiles.on = True
    f0, u0 = tr.total_frames, tr.total_updates
    t0 = time.perf_counter()
    if trace:
        hist = tr.train(max_frames=f0 + per_dispatch * traced_dispatches(mix))
    else:
        hist = tr.train(max_seconds=seconds)
    jax.block_until_ready(tr.state)
    window_s = time.perf_counter() - t0
    compiles.on = False
    if trace:
        jax.profiler.stop_trace()
    frames, updates = tr.total_frames - f0, tr.total_updates - u0
    memory = peak_bytes(devices)
    stats = hist.runtime_stats
    failed = sum(int(stats.get(k) or 0) for k in FAULTS) + len(
        stats.get("degraded") or [])
    log(f"window: {frames / per_dispatch:.0f} dispatches, {frames} env "
        f"frames, {updates * mix['batch_size']} samples updated in "
        f"{window_s:.4f} s; update rate "
        f"{updates * mix['batch_size'] / window_s:.6g} samples/s; "
        f"{len(hist.eval_returns)} evals; {compiles.n} compiles in window")
    run = {"cell": cell, "config": cfg, "traffic": mix,
           "chips": cell["chips"], "device_kind": devices[0].device_kind,
           "frames": frames, "updates": updates,
           "dispatches": frames // per_dispatch, "window_s": window_s}
    del tr, hist
    gc.collect()

    result = {"correct": False, "attempted": frames // per_dispatch,
              "failed": failed, "metrics": {}, "device": {
                  "platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": memory}}
    if trace:
        import reduce_trace
        reduced = reduce_trace.reduce(reduce_trace.load(tdir), len(devices),
                                      window_s)
        shutil.rmtree(tdir, ignore_errors=True)
        run["trace"] = reduced
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        for m in cat.metrics("per_layer", cell):
            value = cat.reader(m["name"])(run)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = reduced["breakdown"]
    else:
        e2e = {"env_frames_per_s": frames / window_s, "setup_s": setup_s}
        for m in cat.metrics("end_to_end", cell):
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}

    t = time.perf_counter()
    ref = pipeline.follow(cfg, mix, seed, FOLLOWED)
    values = compare.readings(prog, ref)
    checks, ok = compare.checks(values, limits)
    rest = [f"{k} {v!r}" for k, v in values.items() if k not in checks]
    log(f"reference: {time.perf_counter() - t:.2f} s"
        + (f"; not compared here: {', '.join(rest)}" if rest else ""))
    result["correct"] = bool(ok)
    result["checks"] = checks
    return result


def main(argv, t_start):
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cat = Catalog()
    cell, cfg, mix, limits = cat.workload(args.workload)
    # the compile cache lives at a fixed path inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cat.root,
                                                           ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"no TPU for {args.workload}: it needs {cell['chips']} TPU "
            f"chip(s), JAX found {len(devices)} {devices[0].platform} "
            f"device(s)")
        return 1
    sys.path.insert(0, os.path.join(cat.root, "src"))
    result = run_cell(cat, cell, cfg, mix, limits, args.seed, args.seconds,
                      bool(args.trace), devices[:cell["chips"]], t_start)
    emit(result)
    return 0


def emit(result):
    """The compared numbers beside their limits as the last lines on
    standard error, then the result as the last line on standard
    output."""
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
