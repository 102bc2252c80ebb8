#!/usr/bin/env python3
"""Smoke run of the Spreeze training main path on a TPU — not a benchmark.

Drives ``SpreezeConfig`` / ``SpreezeTrainer.train`` (the fused megastep
with the Pallas replay kernels compiled by Mosaic) at the paper's sizes
in one process and checks what comes out. The numbers it prints
(compile and run seconds, ``sampling_hz``, ``update_frame_hz``) come from
a few dispatches and are labelled ``smoke``: they show the path runs,
they are not measurements.

    python3 chip_smoke.py             # one chip: phases a, b, c, d
    python3 chip_smoke.py --chips 4   # 2x2 ("ac","batch") mesh path only

Phases on one chip:
  a. SAC, uniform replay, ``pendulum``;
  b. SAC, PER (alpha 0.6, beta 0.4), 3-step returns, ``hopper``;
  c. one megastep with the Pallas ring and one with the jnp ring from
     the same state and key, for a's and b's configurations: rings (and
     PER priority vectors) bitwise equal, parameters and losses within
     1e-5 relative; then one PER draw with the kernels on and off from
     the same pool and key picks the same indices, rows and weights and
     re-prioritises them identically;
  d. ``per_topk`` (score pass, threshold top-k) against the dense
     ``per_topk_ref`` over a window of ``replay_capacity`` rows with k =
     ``batch_size``, on continuous scores, coarse ties straddling the
     k-th place, +0.0 / -0.0 scores with the k-th among either, and a
     partly filled window: scores and priorities bit for bit, indices
     on every finite-score slot.
With ``--chips 4``: SAC uniform (``pendulum``) and PER (``hopper``) on a
2x2 mesh under placement ``ac`` (paper Fig. 2b) and ``dp`` (Fig. 2a),
each against the single-device megastep on device 0 from the same state
and key, held to what ``tests/sharded_check.py`` holds it to (see
``mesh_phase``).

Every phase requires ``tpu_custom_call`` (the Mosaic kernels) in the
compiled megastep, finite losses, and no worker restart, hang,
degradation or rollback. It exits nonzero, printing no result, when JAX
finds no TPU, when the package is missing, or when a check fails. Its
last line on success is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: the paper's deployment sizes (batch 8192) with a 2^20-row ring
SIZES = dict(num_envs=1024, chunk_len=32, batch_size=8192,
             replay_capacity=1_048_576, updates_per_round=8,
             rounds_per_dispatch=4)
UNIFORM = dict(env_name="pendulum")
PER = dict(env_name="hopper", prioritized=True, per_alpha=0.6, per_beta=0.4,
           nstep=3)
TIMED_DISPATCHES = 5
RUNTIME_FAULTS = ("worker_restarts", "worker_hangs", "degraded", "rollbacks")


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(phase, **kw):
    print(json.dumps({"smoke": phase, **kw}, default=float), flush=True)


def _config(sizes, **kw):
    from repro.core import SpreezeConfig
    return SpreezeConfig(**{**sizes, "use_pallas": True, "seed": 0, **kw})


def _compile(tr):
    """AOT-compile the trainer's megastep; -> (seconds, HLO text). The
    jit dispatch that follows finds it in the persistent cache."""
    t0 = time.perf_counter()
    text = tr._megastep.lower(tr.state, tr.replay, tr.env_states,
                              tr.key).compile().as_text()
    return time.perf_counter() - t0, text


def _check_kernels(phase, text, kernels_on=True):
    check(("tpu_custom_call" in text) == kernels_on,
          f"{phase}: tpu_custom_call {'missing from' if kernels_on else 'in'}"
          f" the compiled megastep")


def _peak_bytes():
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def _dispatch(tr):
    (tr.state, tr.replay, tr.env_states, tr.key,
     tr.last_metrics) = tr._megastep(tr.state, tr.replay, tr.env_states,
                                     tr.key)


def _losses(tr):
    import numpy as np
    closs = np.asarray(tr.last_metrics["critic_loss"])
    check(np.isfinite(closs).all(), f"non-finite critic loss {closs}")
    return closs.tolist()


def train_phase(phase, sizes, dispatches=TIMED_DISPATCHES, **kw):
    """Warm up, compile, then ``dispatches`` timed megastep dispatches
    through ``SpreezeTrainer.train`` with the async eval runtime on."""
    import numpy as np

    from repro.core import SpreezeTrainer
    cfg = _config(sizes, async_eval=True,
                  eval_every_rounds=sizes["rounds_per_dispatch"], **kw)
    tr = SpreezeTrainer(cfg)
    compile_s, text = _compile(tr)
    _check_kernels(phase, text)
    frames = cfg.num_envs * cfg.chunk_len * cfg.rounds_per_dispatch
    t0 = time.perf_counter()
    hists = [tr.train(max_frames=1),                  # replay warmup
             tr.train(max_frames=tr.total_frames + frames)]  # 1st dispatch
    warm_s = time.perf_counter() - t0
    f0 = tr.total_frames
    hist = tr.train(max_seconds=600, max_frames=f0 + dispatches * frames)
    for h in hists + [hist]:
        bad = {k: h.runtime_stats.get(k) for k in RUNTIME_FAULTS
               if h.runtime_stats.get(k)}
        check(not bad, f"{phase}: supervisor recorded {bad}")
    check(tr.total_frames - f0 == dispatches * frames,
          f"{phase}: ran {(tr.total_frames - f0) // frames} of "
          f"{dispatches} dispatches")
    check(hist.eval_returns and np.isfinite(hist.eval_returns).all(),
          f"{phase}: eval returns {hist.eval_returns}")
    log(phase, label="smoke run, not a benchmark", compile_s=compile_s,
        warmup_and_first_dispatch_s=warm_s, timed_dispatches=dispatches,
        run_s=hist.wall_s, sampling_hz=hist.sampling_hz,
        update_frame_hz=hist.update_frame_hz,
        critic_loss=_losses(tr),
        mean_rew=np.asarray(tr.last_metrics["mean_rew"]).tolist(),
        eval_returns=hist.eval_returns, runtime_stats=hist.runtime_stats,
        peak_bytes_in_use=_peak_bytes())


def _assert_close(what, a, b, rtol, atol=0.0):
    import numpy as np
    try:
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol)
    except AssertionError as e:
        raise SmokeFailure(f"{what}: {e}") from None


def _assert_trees_close(what, ta, tb, rtol, atol=0.0):
    import jax
    for a, b in zip(jax.tree.leaves(ta), jax.tree.leaves(tb), strict=True):
        _assert_close(what, a, b, rtol, atol)


def _per_draw(tr, replay, pallas, rules=None):
    """One PER draw from ``replay`` and the re-prioritisation of the
    drawn rows (synthetic TD errors), traced under the given kernel
    switch and mesh rules -> (HLO text, (batch, idx, weights,
    priorities))."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from repro.distributed.sharding import use_rules
    from repro.kernels import ops as kops
    from repro.replay import prioritized
    cfg, key = tr.cfg, jax.random.key(1)

    def draw(r, k):
        batch, idx, w = prioritized.sample(
            r, k, cfg.batch_size, alpha=cfg.per_alpha, beta=cfg.per_beta)
        td = jnp.arange(cfg.batch_size, dtype=jnp.float32) / 7.0 - 3.0
        return (batch, idx, w,
                prioritized.update_priorities(r, idx, td).priorities)

    ctx = use_rules(rules) if rules is not None else contextlib.nullcontext()
    with ctx, kops.use_pallas(pallas):
        c = jax.jit(draw).lower(replay, key).compile()   # a fresh trace
    return c.as_text(), c(replay, key)


def _same_draws(what, got, want):
    """PER draws from the same pool and key: identical indices, rows and
    re-prioritised vectors; weights to ``sharded_check``'s 1e-6."""
    import numpy as np
    (b_got, i_got, w_got, p_got), (b_want, i_want, w_want, p_want) = (
        got, want)
    check(np.array_equal(np.asarray(i_got), np.asarray(i_want)),
          f"{what}: PER drew different indices")
    for k in b_want:
        check(np.array_equal(np.asarray(b_got[k]), np.asarray(b_want[k])),
              f"{what}: PER batch field {k!r} differs")
    _assert_close(f"{what}: importance weights", w_got, w_want, 1e-6)
    check(np.array_equal(np.asarray(p_got), np.asarray(p_want)),
          f"{what}: re-prioritised vectors differ")
    return int(np.unique(np.asarray(i_got)).size)


def pallas_vs_jnp_phase(phase, sizes, **kw):
    """One megastep with the Pallas ring and one with the jnp ring, from
    the same state and key. Under PER the priority vectors must match
    bitwise too, and a PER draw from the resulting pool must pick the
    same indices on both paths."""
    import jax
    import numpy as np

    from repro.core import SpreezeTrainer
    cfg = dict(eval_every_rounds=0, **kw)
    trs = [SpreezeTrainer(_config(sizes, **cfg)),
           SpreezeTrainer(_config(sizes, **{**cfg, "use_pallas": False}))]
    out = {}
    for tr in trs:
        tr._warmup()
        on = tr.use_pallas
        compile_s, text = _compile(tr)
        _check_kernels(phase, text, kernels_on=on)
        out[f"compile_s_{'pallas' if on else 'jnp'}"] = compile_s
    pal, ref = trs
    for a, b in zip(jax.tree.leaves((pal.replay, pal.state, pal.env_states,
                                     pal.key)),
                    jax.tree.leaves((ref.replay, ref.state, ref.env_states,
                                     ref.key)), strict=True):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              f"{phase}: states after warmup differ")
    for tr in trs:
        t0 = time.perf_counter()
        _dispatch(tr)
        jax.block_until_ready(tr.state)
        out[f"run_s_{'pallas' if tr.use_pallas else 'jnp'}"] = (
            time.perf_counter() - t0)
    ring, ring_ref = (getattr(t.replay, "base", t.replay) for t in trs)
    check(np.array_equal(np.asarray(ring.rows), np.asarray(ring_ref.rows)),
          f"{phase}: replay rings differ bitwise")
    check(int(ring.ptr) == int(ring_ref.ptr), f"{phase}: ring ptr")
    if cfg.get("prioritized"):
        check(np.array_equal(np.asarray(pal.replay.priorities),
                             np.asarray(ref.replay.priorities)),
              f"{phase}: priority vectors differ bitwise")
        draws = []
        for on in (True, False):
            text, d = _per_draw(pal, pal.replay, on)
            _check_kernels(f"{phase}: PER draw", text, kernels_on=on)
            draws.append(d)
        out["per_unique_draws"] = _same_draws(phase, *draws)
    _assert_trees_close(f"{phase}: params", pal.state, ref.state, 1e-5)
    _assert_close(f"{phase}: critic loss", pal.last_metrics["critic_loss"],
                  ref.last_metrics["critic_loss"], 1e-5)
    log(phase, label="smoke run, not a benchmark", rings_bitwise_equal=True,
        critic_loss_pallas=_losses(pal), critic_loss_jnp=_losses(ref),
        peak_bytes_in_use=_peak_bytes(), **out)


def _topk_window(kind, rows, seed=0):
    """(priorities, gumbel, alpha) whose scores are of the given kind
    (see ``per_topk_phase``)."""
    import jax
    import jax.numpy as jnp
    k1, k2 = jax.random.split(jax.random.key(seed))
    slot = jnp.arange(rows)
    pri = jax.random.uniform(k1, (rows,)) + 0.01
    g = jax.random.gumbel(k2, (rows,))
    if kind == "ties":                  # a grid of 1/4: long runs of ties
        return jnp.where(slot % 7 == 0, 2.0, 1.0), jnp.round(g * 4) / 4, 0.6
    if kind.startswith("signed_zero"):  # alpha 0: -0.0, +0.0 and 1.0
        pos = 10 if kind.endswith("pos") else 6     # +0.0s per 1000 rows
        return (jnp.full((rows,), 0.5),
                jnp.where(slot % 1000 == 0, 1.0,
                          jnp.where(slot % 1000 <= pos, 0.0, -0.0)), 0.0)
    if kind == "partial":               # fewer live rows than k
        return jnp.where(slot < 5000, pri, 0.0), g, 0.6
    return pri, g, 0.6


def per_topk_phase(phase, sizes):
    """``per_topk`` against ``per_topk_ref`` at the PER window's size."""
    import jax
    import numpy as np

    from repro.kernels import replay_ops as rops
    rows, k = sizes["replay_capacity"], sizes["batch_size"]
    out = {}
    for kind in ("continuous", "ties", "signed_zero_neg", "signed_zero_pos",
                 "partial"):
        pri, g, alpha = _topk_window(kind, rows)
        got, want = (
            [np.asarray(x) for x in jax.jit(
                lambda p, n: fn(p, n, alpha, k))(pri, g)]
            for fn in (rops.per_topk, rops.per_topk_ref))
        what = f"{phase}/{kind}"
        check(np.array_equal(got[0].view(np.int32), want[0].view(np.int32)),
              f"{what}: scores differ")
        fin = np.isfinite(want[0])
        check(np.array_equal(got[1][fin], want[1][fin]),
              f"{what}: indices differ")
        check((got[1][~fin] == rops.IDX_SENTINEL).all(),
              f"{what}: -inf slots without the sentinel")
        check(np.array_equal(got[2][fin], want[2][fin]),
              f"{what}: priorities differ")
        zero = want[0] == 0
        out[kind] = dict(finite=int(fin.sum()),
                         neg_zero=int((zero & np.signbit(want[0])).sum()),
                         pos_zero=int((zero & ~np.signbit(want[0])).sum()),
                         kth=float(want[0][-1]))
    log(phase, label="smoke run, not a benchmark", rows=rows, k=k,
        bit_equal=True, **out)


def _max_gap(ta, tb):
    import jax
    import numpy as np
    return max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
               for a, b in zip(jax.tree.leaves(ta), jax.tree.leaves(tb),
                               strict=True))


def mesh_phase(phase, sizes, mesh, **kw):
    """The sharded megastep under placements ``ac`` and ``dp`` against
    the single-device megastep on device 0, from the same state and key,
    held to what ``tests/sharded_check.py`` holds it to: ring bookkeeping
    and PRNG key exact; with uniform replay, parameters and losses within
    rtol 1e-3 / atol 1e-5; with PER, a draw and re-prioritisation from
    the sharded pool identical to the single-device one on the same pool
    (PER over a 2^20-row pool is too sensitive to compare parameters
    after 32 updates: a last-bit difference in a TD error reorders
    near-tied Gumbel scores, so the batches part ways; the gaps are
    logged).

    Both sides run with float32 matmuls at ``highest`` precision: at the
    TPU's default (bfloat16 passes) a last-bit difference from the
    sharded summation order can become a bfloat16 step, where the CPU
    the tolerances were set on has exact float32 matmuls."""
    import jax
    import numpy as np

    from repro.core import SpreezeTrainer
    per = kw.get("prioritized", False)
    with jax.default_matmul_precision("highest"):
        ref = SpreezeTrainer(_config(sizes, eval_every_rounds=0, **kw))
        ref._warmup()
        compile_s, text = _compile(ref)
        _check_kernels(phase, text)
        _dispatch(ref)
        for placement in ("ac", "dp"):
            what = f"{phase}/{placement}"
            tr = SpreezeTrainer(_config(sizes, eval_every_rounds=0,
                                        mesh=mesh, placement=placement,
                                        **kw))
            tr._warmup()
            sh_compile_s, text = _compile(tr)
            _check_kernels(what, text)
            t0 = time.perf_counter()
            _dispatch(tr)
            jax.block_until_ready(tr.state)
            run_s = time.perf_counter() - t0
            ring, ring_ref = (getattr(t.replay, "base", t.replay)
                              for t in (tr, ref))
            check(int(ring.ptr) == int(ring_ref.ptr), f"{what}: ptr")
            check(int(ring.size) == int(ring_ref.size), f"{what}: size")
            check(np.array_equal(np.asarray(tr.key), np.asarray(ref.key)),
                  f"{what}: PRNG key")
            _losses(tr)
            out = dict(
                param_gap=_max_gap((ref.state.actor, ref.state.q),
                                   (tr.state.actor, tr.state.q)),
                critic_loss_sharded=_losses(tr),
                critic_loss_single=_losses(ref))
            if per:
                out["priority_gap"] = _max_gap(ref.replay.priorities,
                                               tr.replay.priorities)
                text, got = _per_draw(tr, tr.replay, True, tr._rules())
                _check_kernels(f"{what}: PER draw", text)
                single = jax.device_put(tr.replay, jax.devices()[0])
                text, want = _per_draw(tr, single, True)
                _check_kernels(f"{what}: PER draw", text)
                out["per_unique_draws"] = _same_draws(what, got, want)
                del single, got, want
            else:
                _assert_trees_close(f"{what}: actor", ref.state.actor,
                                    tr.state.actor, 1e-3, 1e-5)
                _assert_trees_close(f"{what}: q", ref.state.q, tr.state.q,
                                    1e-3, 1e-5)
                _assert_close(f"{what}: critic loss",
                              ref.last_metrics["critic_loss"],
                              tr.last_metrics["critic_loss"], 1e-3, 1e-5)
            log(what, label="smoke run, not a benchmark",
                mesh=dict(mesh.shape), compile_s_single=compile_s,
                compile_s_sharded=sh_compile_s, run_s_sharded=run_s,
                matches_single_device=True, peak_bytes_in_use=_peak_bytes(),
                **out)
            del tr
            gc.collect()


def run(chips, sizes=SIZES):
    import jax
    if chips == 4:
        from repro.launch.mesh import make_ac_mesh
        mesh = make_ac_mesh(2, 2)
        mesh_phase("4chip-uniform-pendulum", sizes, mesh, **UNIFORM)
        mesh_phase("4chip-per-hopper", sizes, mesh, **PER)
    else:
        train_phase("a-uniform-pendulum", sizes, **UNIFORM)
        gc.collect()
        train_phase("b-per-nstep3-hopper", sizes, **PER)
        gc.collect()
        pallas_vs_jnp_phase("c-pallas-vs-jnp-ring", sizes, **UNIFORM)
        gc.collect()
        pallas_vs_jnp_phase("c-pallas-vs-jnp-per-hopper", sizes, **PER)
        gc.collect()
        per_topk_phase("d-per-topk-vs-dense", sizes)
    d = jax.devices()
    return {"ok": True, "device": {"platform": d[0].platform,
                                   "kind": d[0].device_kind,
                                   "count": len(d)}}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2 mesh path")
    args = ap.parse_args()
    # the TPU compiler logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform!r} "
              f"devices)", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 1
    log("device", platform=devs[0].platform, kind=devs[0].device_kind,
        count=len(devs), jax=jax.__version__,
        compilation_cache=jax.config.jax_compilation_cache_dir)
    result = run(args.chips)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
