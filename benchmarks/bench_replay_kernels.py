"""Replay-ring kernel bench: blocked vs row-loop vs jnp, oracle-checked.

Regresses the PR-3 blocked/double-buffered ring kernels against the PR-1
row-at-a-time kernels and the jnp scatter/gather oracles at ring-scale
shapes, and records the PER score-pass arms. Every arm is verified
against its oracle first — any mismatch exits non-zero, which is the CI
smoke contract (a kernel that got faster by reading the wrong rows is
not a win). Writes ``BENCH_replay_kernels.json`` at the repo root.

Wall time on CPU runs the kernels through the Pallas interpreter (the
kernel body lowered op-by-op), so absolute numbers are NOT the TPU
story — the entries exist to (1) exercise the blocked/windowed code
paths at realistic shapes, (2) pin the jnp-oracle XLA-CPU numbers the
throughput tables build on, and (3) let future PRs diff the arms.

Run: ``PYTHONPATH=src python -m benchmarks.bench_replay_kernels
[--tiny | --chip]``; ``--tiny`` is the CI smoke preset, ``--chip`` runs
the PER arms alone at the trainer's TPU sizes (give it ``--out``).
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_call
from repro.kernels import ops as kops
from repro.kernels import replay_ops as rops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _check(name: str, got, want, atol=0.0) -> bool:
    ok = np.allclose(np.asarray(got), np.asarray(want), atol=atol)
    if not ok:
        print(f"ORACLE MISMATCH in {name}", file=sys.stderr)
    return ok


def bench_ring_write(cap, n, feat, iters) -> dict:
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    data = jax.random.normal(k1, (cap, feat))
    batch = jax.random.normal(k2, (n, feat))
    ptr = jnp.asarray(cap - n // 2, jnp.int32)      # wraps mid-write
    want = rops.ring_write_ref(data, batch, ptr)
    arms = {
        "blocked": jax.jit(functools.partial(rops.ring_write)),
        "rowloop": jax.jit(functools.partial(rops.ring_write_rowloop)),
        "jnp": jax.jit(rops.ring_write_ref),
    }
    rec, ok = {}, True
    for name, fn in arms.items():
        ok &= _check(f"ring_write/{name}", fn(data, batch, ptr), want)
        rec[f"{name}_ms"] = round(
            time_call(lambda fn=fn: fn(data, batch, ptr), iters) * 1e3, 3)
    return rec, ok


def bench_ring_gather(cap, bsz, feat, iters) -> dict:
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    data = jax.random.normal(k1, (cap, feat))
    idx = jax.random.randint(k2, (bsz,), 0, cap)
    want = jnp.take(data, idx, axis=0)
    arms = {
        "blocked": jax.jit(functools.partial(rops.ring_gather)),
        "rowloop": jax.jit(functools.partial(rops.ring_gather_rowloop)),
        "jnp": jax.jit(lambda d, i: jnp.take(d, i, axis=0)),
    }
    rec, ok = {}, True
    for name, fn in arms.items():
        ok &= _check(f"ring_gather/{name}", fn(data, idx), want)
        rec[f"{name}_ms"] = round(
            time_call(lambda fn=fn: fn(data, idx), iters) * 1e3, 3)
    return rec, ok


def bench_per_topk(cap, k, iters) -> dict:
    """Score pass + threshold top-k vs the dense jnp oracle. Scores must
    match the oracle bit-for-bit; indices match on
    every finite-score slot (-inf slots carry ``IDX_SENTINEL`` in the
    kernel — unspecified and unused, see ``replay.prioritized``). Also
    oracle-checks the two-phase form itself (4 windows + candidate
    merge == dense top-k) and, when the process has >= 8 devices (the
    sharded CI job), the ``per_topk_sharded`` mesh wrapper."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    pri = jnp.where(jax.random.uniform(k1, (cap,)) > 0.5,
                    jax.random.uniform(k1, (cap,)) + 0.1, 0.0)
    g = jax.random.gumbel(k2, (cap,))
    want_v, want_i, _ = rops.per_topk_ref(pri, g, 0.6, k)
    fin = np.isfinite(np.asarray(want_v))

    def check_sel(name, got) -> bool:
        v, i = got[:2]
        ok = _check(f"per_topk/{name}/scores", v, want_v)
        ok &= _check(f"per_topk/{name}/idx", np.asarray(i)[fin],
                     np.asarray(want_i)[fin])
        return ok

    arms = {
        "blocked": jax.jit(lambda p, n: rops.per_topk(p, n, 0.6, k)),
        "jnp": jax.jit(lambda p, n: rops.per_topk_ref(p, n, 0.6, k)),
    }
    rec, ok = {}, True
    for name, fn in arms.items():
        ok &= check_sel(name, fn(pri, g))
        rec[f"{name}_ms"] = round(
            time_call(lambda fn=fn: fn(pri, g), iters) * 1e3, 3)

    # two-phase oracle: 4 window-local top-k's + fixed-order merge must
    # equal the dense global top-k (the layout-invariance identity)
    rows = cap // 4
    cand = [rops.per_topk(pri[lo:lo + rows], g[lo:lo + rows], 0.6, k,
                          window_start=lo) for lo in range(0, cap, rows)]
    ok &= check_sel("two_phase_merge", rops.merge_topk_candidates(
        *(jnp.concatenate([c[j] for c in cand]) for j in range(3)), k))

    if len(jax.devices()) >= 8:
        from repro.distributed.sharding import trainer_rules, use_rules
        from repro.launch.mesh import make_ac_mesh
        rules = trainer_rules(make_ac_mesh(2, 4), "ac")
        with use_rules(rules):
            got = jax.jit(lambda p, n: kops.per_topk_sharded(
                p, n, 0.6, k, rules))(pri, g)
        sharded_ok = check_sel("sharded", got)
        rec["sharded_ok"] = bool(sharded_ok)
        ok &= sharded_ok
    return rec, ok


def bench_per_scores(cap, iters) -> dict:
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    # half-empty pool: the masked (-inf) path is exercised, not skipped
    pri = jnp.where(jax.random.uniform(k1, (cap,)) > 0.5,
                    jax.random.uniform(k1, (cap,)) + 0.1, 0.0)
    g = jax.random.gumbel(k2, (cap,))
    want = rops.per_scores_ref(pri, g, 0.6)
    arms = {
        "pallas": jax.jit(lambda p, n: rops.per_scores(p, n, 0.6)),
        "jnp": jax.jit(lambda p, n: rops.per_scores_ref(p, n, 0.6)),
    }
    rec, ok = {}, True
    for name, fn in arms.items():
        ok &= _check(f"per_scores/{name}", fn(pri, g), want)
        rec[f"{name}_ms"] = round(
            time_call(lambda fn=fn: fn(pri, g), iters) * 1e3, 3)
    return rec, ok


def bench_priority_scatter(cap, k, iters) -> dict:
    """PER re-prioritisation: the HBM read-modify-write kernel with the
    whole vector as one block (the default up to 2^20 rows) and in four
    blocks, against XLA's scatter. Indices are distinct, as PER draws without replacement, so
    every arm must match the oracle bit for bit. Nothing is donated: each
    arm pays one copy of the vector."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    pri = jax.random.uniform(k1, (cap,)) + 0.1
    idx = jax.random.permutation(k2, cap)[:k].astype(jnp.int32)
    val = jnp.arange(k, dtype=jnp.float32) + 2.0
    want = rops.priority_scatter_ref(pri, idx, val)
    arms = {
        "one_block": jax.jit(functools.partial(rops.priority_scatter,
                                               block=cap)),
        "four_blocks": jax.jit(functools.partial(rops.priority_scatter,
                                                 block=cap // 4)),
        "jnp": jax.jit(rops.priority_scatter_ref),
    }
    rec, ok = {}, True
    for name, fn in arms.items():
        ok &= _check(f"priority_scatter/{name}", fn(pri, idx, val), want)
        rec[f"{name}_ms"] = round(
            time_call(lambda fn=fn: fn(pri, idx, val), iters) * 1e3, 3)
    return rec, ok


def main_chip(out: str) -> bool:
    """The PER kernels at the trainer's chip sizes (2^20-row pool, batch
    8192). The ring arms stay out: the row-loop baselines move
    lane-unaligned rows, which Mosaic refuses."""
    cap, k, iters = 1 << 20, 8192, 20
    cfg = {"capacity": cap, "topk": k, "backend": jax.default_backend(),
           "device_kind": jax.devices()[0].device_kind,
           "interpret": jax.default_backend() != "tpu"}
    per_rec, ok_p = bench_per_scores(cap, iters)
    topk_rec, ok_t = bench_per_topk(cap, k, iters)
    scat_rec, ok_s = bench_priority_scatter(cap, k, iters)
    oracle_ok = bool(ok_p and ok_t and ok_s)
    report = {"config": cfg, "per_scores": per_rec, "per_topk": topk_rec,
              "priority_scatter": scat_rec, "oracle_ok": oracle_ok}
    print(json.dumps(report))
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    return oracle_ok


def main(tiny: bool = False,
         out: str = os.path.join(ROOT, "BENCH_replay_kernels.json")) -> bool:
    if tiny:
        cap, n, bsz, feat, iters, k = 2048, 256, 256, 8, 2, 64
    else:
        cap, n, bsz, feat, iters, k = 16384, 1024, 1024, 16, 3, 256
    cfg = {"capacity": cap, "write_rows": n, "gather_rows": bsz,
           "features": feat, "topk": k, "tiny": tiny,
           "backend": jax.default_backend(),
           "interpret": jax.default_backend() != "tpu"}
    write_rec, ok_w = bench_ring_write(cap, n, feat, iters)
    gather_rec, ok_g = bench_ring_gather(cap, bsz, feat, iters)
    per_rec, ok_p = bench_per_scores(cap, iters)
    topk_rec, ok_t = bench_per_topk(cap, k, iters)
    scat_rec, ok_s = bench_priority_scatter(cap, k, iters)
    oracle_ok = bool(ok_w and ok_g and ok_p and ok_t and ok_s)
    emit("replay_kernels", "ring_write", **write_rec)
    emit("replay_kernels", "ring_gather", **gather_rec)
    emit("replay_kernels", "per_scores", **per_rec)
    emit("replay_kernels", "per_topk", **topk_rec)
    emit("replay_kernels", "priority_scatter", **scat_rec)
    emit("replay_kernels", "oracle", ok=oracle_ok)
    report = {"config": cfg, "ring_write": write_rec,
              "ring_gather": gather_rec, "per_scores": per_rec,
              "per_topk": topk_rec, "priority_scatter": scat_rec,
              "oracle_ok": oracle_ok}
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    return oracle_ok


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke preset (small shapes, fewer iters)")
    ap.add_argument("--chip", action="store_true",
                    help="PER kernels only, at the trainer's TPU sizes")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "BENCH_replay_kernels.json"))
    args = ap.parse_args()
    sys.exit(0 if (main_chip(args.out) if args.chip
                   else main(tiny=args.tiny, out=args.out)) else 1)
