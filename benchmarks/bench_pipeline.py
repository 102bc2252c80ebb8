"""Fused megastep vs eager per-round dispatch: the pipeline's perf number.

Times both paths on pendulum+SAC and reports dispatched rounds/s plus
the paper's sampling / update-frame Hz (Tables 2-3 quantities). Writes
``BENCH_pipeline.json`` at the repo root so future PRs have a perf
trajectory to regress against.

The probe config is deliberately **dispatch-bound** (tiny nets, 1 env,
1 update/round): per-round device compute is then comparable to the
per-round host dispatch overhead the megastep eliminates, which is the
quantity under test. On compute-bound production configs the eager
loop's async dispatch already overlaps host and device, so fusion is
neutral there — the win is wherever host re-entry bounds the Hz
(paper's whole thesis, Fig. 4). Arms warm-compile before the timed
window and run ``--repeats`` times (median reported): this container's
CPU is noisy.

A second comparison runs in a child process under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``: the same probe
on a (2, 4) ``ac x batch`` mesh (paper Fig. 2b placement — Q ensemble
sharded over ``ac``, replay rows over ``batch``) vs replicated
single-device dispatch in the same 8-device process. The child process
keeps the original arms' 1-device environment untouched, so the fused
rounds/s entry stays comparable across PRs. On emulated host-CPU
devices the sharded arm pays real cross-"device" copies for tiny
compute, so it is expected to trail the replicated arm here; the entry
records the dispatch overhead of the sharded program, not a GPU/TPU
speedup.

Comparability note: PR 3 switched the repo to partitionable threefry
(``repro/__init__.py`` — jax.random draws must not change value with
tensor layout, or the mesh-native replay kernels can't be verified
against their oracles). Partitionable bit generation costs ~15-20% more
host-CPU time than the legacy impl on this dispatch-bound probe (tiny
nets make RNG a visible fraction; on TPU with production nets it is
noise), so absolute Hz across that boundary aren't comparable — the
fused/unfused RATIO is the stable signal. PR 5 cleaned two more
comparability seams: the probe arms now disable eval outright
(``eval_every_rounds=0``; the old ``10**9`` sentinel still fired one
round-0 eval inside every timed window) and the Hz columns divide
post-warmup frames by post-warmup wall time (the old quotient counted
warmup frames it didn't count the seconds for).

``--mode eval-overlap`` records the paper's Fig. 4b claim — eval and
visualization run fully asynchronously with training — as the
``eval_overlap`` entry of ``BENCH_pipeline.json`` (read-modify-write:
the fused/unfused/sharded entries are left untouched). Three arms on
the same dispatch-bound probe with one eval (4 episodes) gated per
fused dispatch: ``eval_off`` (no eval at all, the ceiling),
``async_eval`` (the host runtime: the train thread publishes the
``overlap_eval`` snapshot into the latest-wins mailbox and keeps
dispatching), and ``inline`` (the pre-runtime behavior: the loop blocks
on ``float(eval_batch(...))`` every window). Each arm reports
``rounds_per_s``, the cumulative train-thread ``eval_blocked_s``, and
``blocked_frac`` (blocked seconds / wall). The claim under test:
async blocked_frac ~ 0 and async rounds/s within noise of eval_off,
while inline shows the gap. ``evals`` / ``eval_dropped`` count how many
snapshots were scored vs replaced in the mailbox (latest-wins).

``--mode queue`` records the paper's Fig. 4a shared-memory-vs-queue gap
as its own regression surface (``BENCH_queue.json``): the same probe on
the host-queue transfer (device->host dump, bounded deque, re-upload —
both endpoints block) vs the shared-memory eager loop, including the
host queue's Table-3 columns (``transfer_cycle`` seconds between drains
and ``transmission_loss`` — the fraction of sampled frames dropped on
queue overflow). The queue arm uses multi-frame chunks into a queue a
few chunks deep, so the drain cycle spans several rounds (stale, bursty
handoffs — the Fig. 4a pathology) instead of the dispatch-bound 1-frame
probe's degenerate empty queue. Note ``transmission_loss`` is
structurally 0 on this geometry: the single-threaded eager loop flushes
after every push, so occupancy tops out at the drain threshold, far
below the cap. The column is tracked as an invariant — it regressing to
nonzero means the loop started dropping experience (e.g. a flush
reordering), exactly what the surface should flag.

Run: ``PYTHONPATH=src python -m benchmarks.bench_pipeline [--seconds S]
[--mode shared|queue]``.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

from benchmarks.common import (child_pythonpath, emit,
                               xla_flags_force_devices)
from repro.core import SpreezeConfig, SpreezeTrainer
from repro.rl.base import AlgoHP

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_arm(fused: bool, seconds: float, rpd: int, repeats: int,
            mesh=None) -> dict:
    cfg = SpreezeConfig(
        env_name="pendulum", algo="sac", num_envs=1, batch_size=32,
        chunk_len=1, updates_per_round=1, warmup_frames=64,
        replay_capacity=4096, eval_every_rounds=0,
        rounds_per_dispatch=rpd, fused=fused, mesh=mesh,
        hp=AlgoHP(algo="sac", hidden=(32, 32)))
    tr = SpreezeTrainer(cfg)
    # warm pass: one dispatch through each compiled path, so the timed
    # window measures steady-state dispatch throughput, not XLA compiles
    tr.train(max_seconds=0.01)
    runs = []
    for _ in range(repeats):
        tr.total_frames = 0
        tr.total_updates = 0
        runs.append(tr.train(max_seconds=seconds))
    hist = sorted(runs, key=lambda h: h.update_hz)[len(runs) // 2]
    # rounds only accrue after warmup, so update_hz is the clean signal
    rounds_per_s = hist.update_hz / cfg.updates_per_round
    return {"fused": fused, "rounds_per_dispatch": rpd if fused else 1,
            "rounds_per_s": round(rounds_per_s, 1),
            "sampling_hz": round(hist.sampling_hz, 1),
            "update_hz": round(hist.update_hz, 1),
            "update_frame_hz": round(hist.update_frame_hz, 1)}


def run_transfer_arm(transfer: str, seconds: float, repeats: int,
                     queue_size: int = 256) -> dict:
    """One eager-loop arm on the given transfer path, with a geometry
    that makes the queue pathology observable: 32-frame sampler chunks
    into a 256-frame queue (drain threshold 128), so the handoff waits
    for a multi-round load and experience reaches the updater in
    stale, bursty batches — the Fig. 4a semantics. On this CPU
    container the host round-trip is cheap, so the paper's throughput
    collapse shows up in ``blocked_time_s`` (host time both endpoints
    lose to the dump/upload — identically 0 on the shared path) and
    ``transfer_cycle_s`` rather than necessarily in rounds/s; all
    three are the tracked columns."""
    from repro.core.transfer import make_transfer

    cfg = SpreezeConfig(
        env_name="pendulum", algo="sac", num_envs=4, batch_size=32,
        chunk_len=8, updates_per_round=1, warmup_frames=64,
        replay_capacity=4096, eval_every_rounds=0,
        transfer=transfer, queue_size=queue_size, fused=False,
        hp=AlgoHP(algo="sac", hidden=(32, 32)))
    tr = SpreezeTrainer(cfg)
    tr.train(max_seconds=0.01)
    runs = []
    for _ in range(repeats):
        tr.total_frames = 0
        tr.total_updates = 0
        # fresh transfer per repeat: the host-queue counters (blocked
        # time, cycle times, offered/dropped frames) are cumulative, so
        # a shared instance would report warmup + every earlier repeat
        # in whichever run lands as the median
        tr.transfer = make_transfer(cfg.transfer, cfg.queue_size)
        runs.append(tr.train(max_seconds=seconds))
    hist = sorted(runs, key=lambda h: h.update_hz)[len(runs) // 2]
    return {"transfer": transfer,
            "rounds_per_s": round(hist.update_hz / cfg.updates_per_round, 1),
            "sampling_hz": round(hist.sampling_hz, 1),
            "update_hz": round(hist.update_hz, 1),
            "update_frame_hz": round(hist.update_frame_hz, 1),
            "transfer_cycle_s": round(
                hist.transfer_stats.get("transfer_cycle_s", 0.0), 6),
            "transmission_loss": round(
                hist.transfer_stats.get("transmission_loss", 0.0), 4),
            "blocked_time_s": round(
                hist.transfer_stats.get("blocked_time_s", 0.0), 4)}


def run_eval_overlap_arm(eval_mode: str, seconds: float, rpd: int,
                         repeats: int) -> dict:
    """One probe arm for the Fig. 4b surface. ``eval_mode``: "off" (no
    eval windows), "async" (host runtime + overlap_eval snapshots), or
    "inline" (the blocking pre-runtime path)."""
    assert eval_mode in ("off", "async", "inline")
    # the async arm carries the PR-9 resilience layer at its defaults —
    # supervision on AND the off-thread snapshot channel at the default
    # cadence — so the Fig. 4b number is the number users actually get
    snap_dir = (tempfile.mkdtemp(prefix="spreeze_snap_bench_")
                if eval_mode == "async" else None)
    cfg = SpreezeConfig(
        env_name="pendulum", algo="sac", num_envs=1, batch_size=32,
        chunk_len=1, updates_per_round=1, warmup_frames=64,
        replay_capacity=4096, rounds_per_dispatch=rpd, fused=True,
        eval_every_rounds=(rpd if eval_mode != "off" else 0),
        eval_episodes=4, async_eval=(eval_mode == "async"),
        overlap_eval=(eval_mode == "async"), snapshot_dir=snap_dir,
        hp=AlgoHP(algo="sac", hidden=(32, 32)))
    tr = SpreezeTrainer(cfg)
    tr.train(max_seconds=0.01)
    runs = []
    for _ in range(repeats):
        tr.total_frames = 0
        tr.total_updates = 0
        runs.append(tr.train(max_seconds=seconds))
    hist = sorted(runs, key=lambda h: h.update_hz)[len(runs) // 2]
    return {"eval_mode": eval_mode,
            "rounds_per_s": round(hist.update_hz / cfg.updates_per_round, 1),
            "sampling_hz": round(hist.sampling_hz, 1),
            "eval_blocked_s": round(hist.eval_blocked_s, 4),
            "blocked_frac": round(
                hist.eval_blocked_s / max(hist.wall_s, 1e-9), 4),
            "evals": len(hist.eval_returns),
            "eval_dropped": int(hist.runtime_stats.get("eval_dropped", 0)),
            "snapshots_written": int(
                hist.runtime_stats.get("state_done", 0))}


def main_eval_overlap(seconds: float = 2.0, rpd: int = 16, repeats: int = 3,
                      out: str = os.path.join(ROOT, "BENCH_pipeline.json")
                      ) -> dict:
    """--mode eval-overlap: train-thread blocked time with eval off /
    async / inline (paper Fig. 4b) -> the ``eval_overlap`` entry of
    BENCH_pipeline.json (other entries preserved)."""
    off = run_eval_overlap_arm("off", seconds, rpd, repeats)
    async_arm = run_eval_overlap_arm("async", seconds, rpd, repeats)
    inline = run_eval_overlap_arm("inline", seconds, rpd, repeats)
    entry = {"seconds_per_arm": seconds, "eval_episodes": 4,
             "eval_every_rounds": rpd,
             "eval_off": off, "async_eval": async_arm, "inline": inline,
             "async_over_off_rounds_per_s": round(
                 async_arm["rounds_per_s"] / max(off["rounds_per_s"], 1e-9),
                 3),
             "inline_over_off_rounds_per_s": round(
                 inline["rounds_per_s"] / max(off["rounds_per_s"], 1e-9),
                 3)}
    for name, arm in (("eval_off", off), ("async_eval", async_arm),
                      ("inline", inline)):
        emit("eval_overlap", name, **arm)
    report = {}
    if os.path.exists(out):
        with open(out) as f:
            report = json.load(f)
    report["eval_overlap"] = entry
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    return report


def main_queue(seconds: float = 2.0, repeats: int = 3,
               out: str = os.path.join(ROOT, "BENCH_queue.json")) -> dict:
    """--mode queue: the shared-memory-vs-host-queue gap (paper Fig. 4a)
    as a tracked surface — same eager loop, only the transfer differs."""
    shared = run_transfer_arm("shared", seconds, repeats)
    queue = run_transfer_arm("queue", seconds, repeats)
    ratio = queue["rounds_per_s"] / max(shared["rounds_per_s"], 1e-9)
    emit("queue", "shared_eager", **shared)
    emit("queue", "queue", **queue)
    emit("queue", "gap", queue_over_shared_rounds_per_s=round(ratio, 3))
    report = {"env": "pendulum", "algo": "sac",
              "seconds_per_arm": seconds,
              "shared_eager": shared, "queue": queue,
              "queue_over_shared_rounds_per_s": round(ratio, 3)}
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    return report


def sharded_child(seconds: float, rpd: int, repeats: int, out: str):
    """Child-process entry (8 forced host devices): sharded mesh arm vs
    replicated single-device arm, dumped to ``out`` as JSON."""
    import jax

    from repro.launch.mesh import make_ac_mesh

    mesh = make_ac_mesh(2, 4)
    sharded = run_arm(True, seconds, rpd, repeats, mesh=mesh)
    replicated = run_arm(True, seconds, rpd, repeats)
    ratio = sharded["rounds_per_s"] / max(replicated["rounds_per_s"], 1e-9)
    rec = {"devices": len(jax.devices()), "mesh": "ac2xbatch4",
           "placement": "ac", "sharded": sharded,
           "replicated": replicated,
           "sharded_over_replicated_rounds_per_s": round(ratio, 3)}
    with open(out, "w") as f:
        json.dump(rec, f)


def run_sharded_comparison(seconds: float, rpd: int, repeats: int) -> dict:
    """Spawn the 8-device child (XLA_FLAGS must precede jax init there).
    Forcing host devices only works on the CPU backend, and on an
    accelerator this process already holds the devices the child would
    need, so there the comparison is refused."""
    import tempfile

    import jax

    if jax.default_backend() != "cpu":
        return {"error": f"the sharded comparison forces 8 CPU devices in "
                         f"a child process; this process holds the "
                         f"{jax.default_backend()!r} devices — rerun with "
                         f"JAX_PLATFORMS=cpu or --no-sharded"}

    out = os.path.join(tempfile.mkdtemp(prefix="spreeze_bench_"),
                       "sharded.json")
    env = dict(os.environ, PYTHONPATH=child_pythonpath(),
               XLA_FLAGS=xla_flags_force_devices(8))
    # 2 arms x (warmup + repeats) timed windows + 8-device compile slack
    budget = max(1200, int(2 * (repeats + 1) * seconds) + 600)
    try:
        r = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_pipeline",
             "--sharded-child", out, "--seconds", str(seconds),
             "--rpd", str(rpd), "--repeats", str(repeats)],
            env=env, cwd=ROOT, capture_output=True, text=True,
            timeout=budget)
    except subprocess.TimeoutExpired:
        # still record the already-measured fused/unfused arms
        return {"error": f"sharded child timed out after {budget}s"}
    if r.returncode != 0:
        return {"error": (r.stderr or r.stdout)[-2000:]}
    with open(out) as f:
        return json.load(f)


def main(seconds: float = 2.0, rpd: int = 16, repeats: int = 3,
         out: str = os.path.join(ROOT, "BENCH_pipeline.json"),
         sharded: bool = True) -> dict:
    unfused = run_arm(False, seconds, rpd, repeats)
    fused = run_arm(True, seconds, rpd, repeats)
    speedup = fused["rounds_per_s"] / max(unfused["rounds_per_s"], 1e-9)
    emit("pipeline", "unfused", **unfused)
    emit("pipeline", "fused", **fused)
    emit("pipeline", "speedup", rounds_per_s_ratio=round(speedup, 2))
    report = {"env": "pendulum", "algo": "sac", "seconds_per_arm": seconds,
              "unfused": unfused, "fused": fused,
              "fused_over_unfused_rounds_per_s": round(speedup, 3)}
    if os.path.exists(out):
        # keep the eval_overlap entry (owned by --mode eval-overlap)
        with open(out) as f:
            prior = json.load(f)
        if "eval_overlap" in prior:
            report["eval_overlap"] = prior["eval_overlap"]
    if sharded:
        comp = run_sharded_comparison(seconds, rpd, repeats)
        report["sharded_comparison"] = comp
        if "error" not in comp:
            emit("pipeline", "sharded", **comp["sharded"])
            emit("pipeline", "replicated", **comp["replicated"])
            emit("pipeline", "sharded_ratio", rounds_per_s_ratio=comp[
                "sharded_over_replicated_rounds_per_s"])
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=2.0,
                    help="wall budget per timed repeat")
    ap.add_argument("--rpd", type=int, default=16,
                    help="rounds_per_dispatch for the fused arm")
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed repeats per arm (median reported)")
    ap.add_argument("--no-sharded", action="store_true",
                    help="skip the 8-device sharded-vs-replicated child")
    ap.add_argument("--mode", choices=("shared", "queue", "eval-overlap"),
                    default="shared",
                    help="shared: fused-vs-eager (BENCH_pipeline.json); "
                         "queue: host-queue baseline (BENCH_queue.json); "
                         "eval-overlap: async-vs-inline eval blocked time "
                         "(eval_overlap entry of BENCH_pipeline.json)")
    ap.add_argument("--sharded-child", default=None, metavar="OUT",
                    help=argparse.SUPPRESS)   # internal child-process mode
    args = ap.parse_args()
    if args.sharded_child:
        sharded_child(args.seconds, args.rpd, args.repeats,
                      args.sharded_child)
    elif args.mode == "queue":
        main_queue(seconds=args.seconds, repeats=args.repeats)
    elif args.mode == "eval-overlap":
        main_eval_overlap(seconds=args.seconds, rpd=args.rpd,
                          repeats=args.repeats)
    else:
        main(seconds=args.seconds, rpd=args.rpd, repeats=args.repeats,
             sharded=not args.no_sharded)
