"""Roofline table: read reports/dryrun/*.json, print the per-(arch x shape
x mesh) three-term roofline with bottleneck + useful-flops ratio.

Run ``python -m repro.launch.dryrun --all [--multipod]`` first; this
module only aggregates (it never initializes 512 devices itself).

``--megastep`` is a separate surface: the three-term roofline +
collective-bytes census of the COMPILED sharded trainer megastep (PER
and uniform arms on an ac2 x batch4 mesh, Pallas kernels on), written to
``BENCH_roofline.json`` at the repo root. It asserts the PR-4 contract
on the lowered HLO: the PER path adds no collective whose result is
proportional to the replay capacity — the only PER-specific cross-group
traffic is the ``(groups * batch,)`` top-k candidate merge plus
scalar/batch-sized reductions. Any capacity-sized collective in the
PER-minus-uniform delta fails the run (non-zero exit — the CI smoke
contract). Needs >= 8 host devices; when the process has fewer it
re-execs itself in a child with the device count forced.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

REPORT_DIR = os.path.join(os.path.dirname(__file__), "..", "reports",
                          "dryrun")

COLS = ("arch", "shape", "mesh", "compute_s", "memory_s", "collective_s",
        "bottleneck", "useful_ratio", "peak_memory_per_device")


def load(report_dir: str = REPORT_DIR) -> List[Dict]:
    recs = []
    for f in sorted(glob.glob(os.path.join(report_dir, "*.json"))):
        with open(f) as fh:
            recs.append(json.load(fh))
    return recs


def fmt_row(r: Dict) -> str:
    if "skipped" in r:
        return (f"{r['arch']:<18} {r['shape']:<12} {r['mesh']:<8} "
                f"SKIP ({r['skipped'][:60]}...)")
    if "error" in r:
        return (f"{r['arch']:<18} {r['shape']:<12} "
                f"ERROR {r['error'][:70]}")
    gib = r["peak_memory_per_device"] / 2**30
    return (f"{r['arch']:<18} {r['shape']:<12} {r['mesh']:<8} "
            f"{r['compute_s']:.3e} {r['memory_s']:.3e} "
            f"{r['collective_s']:.3e}  {r['bottleneck']:<10} "
            f"{r['useful_ratio']:.3f}  {gib:7.2f}")


# --------------------------------------------------------------------------- #
# --megastep: roofline + collective census of the compiled trainer megastep
# --------------------------------------------------------------------------- #

def _megastep_arm(mesh, *, prioritized: bool, capacity: int,
                  batch_size: int) -> Dict:
    """Compile one sharded megastep (Pallas on) and read its artifact."""
    from repro.core import SpreezeConfig, SpreezeTrainer
    from repro.launch import analysis

    cfg = SpreezeConfig(
        env_name="pendulum", algo="sac", num_envs=2, batch_size=batch_size,
        chunk_len=4, updates_per_round=2, rounds_per_dispatch=2,
        warmup_frames=64, replay_capacity=capacity,
        eval_every_rounds=10**9, mesh=mesh, use_pallas=True,
        prioritized=prioritized, seed=3)
    tr = SpreezeTrainer(cfg)
    compiled = tr._megastep.lower(tr.state, tr.replay, tr.env_states,
                                  tr.key).compile()
    hlo = compiled.as_text()
    cost = compiled.cost_analysis()
    coll = analysis.collective_bytes(hlo)
    roof = analysis.Roofline(
        arch="spreeze_megastep",
        shape=f"pendulum-sac-b{batch_size}-cap{capacity}"
              f"{'-per' if prioritized else ''}",
        mesh="x".join(f"{a}{n}" for a, n in mesh.shape.items()),
        chips=mesh.size,
        flops_per_device=float(cost.get("flops", 0.0)),
        bytes_per_device=float(cost.get("bytes accessed", 0.0)),
        collective_bytes_per_device=float(coll["total"])).finalize()
    return {"prioritized": prioritized,
            "roofline": roof.to_dict(),
            "collective_bytes": {k: v for k, v in coll.items() if v},
            "collective_shapes": [
                [kind, list(dims)] for kind, dims
                in analysis.collective_result_shapes(hlo)],
            "scan_trip_count": analysis.scan_trip_counts(hlo)}


def megastep_report(out: str) -> bool:
    """PER vs uniform megastep rooflines + the capacity-collective
    assertion on their delta. Returns True iff the contract holds."""
    import jax

    from repro.kernels import replay_ops as rops
    from repro.launch.mesh import make_ac_mesh

    capacity, batch_size = 4096, 64
    mesh = make_ac_mesh(2, 4)
    base = _megastep_arm(mesh, prioritized=False, capacity=capacity,
                         batch_size=batch_size)
    rops.reset_trace_counts()
    per = _megastep_arm(mesh, prioritized=True, capacity=capacity,
                        batch_size=batch_size)
    per["trace_counts"] = {k: v for k, v in rops.TRACE_COUNTS.items()}

    # the PER-minus-uniform collective delta: every shape the PER path
    # ADDS must be sub-capacity (candidate merges are (groups*batch,),
    # weight combines (batch/groups, 1), the rest scalars) — a
    # capacity-sized entry here means selection went global again.
    # Since PR 8 the predicate is the shared hlolint analyzer
    # (checks.shape_delta / capacity_offenders) — the same code that
    # enforces the standing megastep_sharded_per contract.
    from repro.analysis.hlolint import checks
    added = checks.shape_delta(per["collective_shapes"],
                               base["collective_shapes"])
    offenders = checks.capacity_offenders(added, capacity)
    groups = mesh.shape["batch"]
    ok = (not offenders
          and per["trace_counts"].get("shard:per_topk", 0) > 0)
    bytes_delta = (per["collective_bytes"].get("total", 0)
                   - base["collective_bytes"].get("total", 0))
    report = {
        "devices": len(jax.devices()),
        "capacity": capacity, "batch_size": batch_size,
        "batch_groups": groups,
        "candidate_merge_elems": groups * batch_size,
        "base": base, "per": per,
        "per_added_collective_shapes": added,
        "per_collective_bytes_delta": bytes_delta,
        "capacity_sized_collectives_added": offenders,
        "ok": bool(ok),
    }
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"[roofline] megastep: bytes_delta={bytes_delta} "
          f"added_shapes={len(added)} offenders={offenders} ok={ok}")
    return bool(ok)


def run_megastep(out: str) -> bool:
    """Entry for --megastep: in-process when the host already has >= 8
    devices, else a child process with 8 CPU devices forced (the flag
    must precede jax initialization). The flag only grows the CPU
    platform, and a parent on an accelerator holds its devices, so a
    child there could not start: that case is refused."""
    import jax

    if len(jax.devices()) >= 8:
        return megastep_report(out)
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"the megastep roofline needs 8 devices; this "
            f"{jax.default_backend()!r} host has {len(jax.devices())}, and "
            "forcing host devices only works on the CPU backend — run "
            "with JAX_PLATFORMS=cpu")
    from benchmarks.common import child_pythonpath, xla_flags_force_devices
    env = dict(os.environ, PYTHONPATH=child_pythonpath(),
               XLA_FLAGS=xla_flags_force_devices(8))
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.roofline", "--megastep",
         "--out", out], env=env, cwd=ROOT, timeout=1800)
    return r.returncode == 0


def main(report_dir: str = REPORT_DIR):
    recs = load(report_dir)
    if not recs:
        print("no dry-run reports found; run "
              "`python -m repro.launch.dryrun --all` first")
        return
    print(f"{'arch':<18} {'shape':<12} {'mesh':<8} "
          f"{'compute_s':>9} {'memory_s':>9} {'coll_s':>9}  "
          f"{'bottleneck':<10} {'useful':>6} {'GiB/dev':>8}")
    for r in recs:
        if "mode" in r:       # spreeze RL / arch records have their own shape
            print(f"[{r['mode']}] " + ", ".join(
                f"{k}={v}" for k, v in r.items()
                if k in ("arch", "algo", "mesh", "placement", "batch",
                         "collective_bytes_per_device")))
            continue
        print(fmt_row(r))
    # bottleneck census
    census: Dict[str, int] = {}
    for r in recs:
        b = r.get("bottleneck")
        if b:
            census[b] = census.get(b, 0) + 1
    print("\nbottleneck census:", census)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default=REPORT_DIR)
    ap.add_argument("--megastep", action="store_true",
                    help="compiled-megastep roofline + PER collective "
                         "assertion -> BENCH_roofline.json")
    ap.add_argument("--out",
                    default=os.path.join(ROOT, "BENCH_roofline.json"))
    args = ap.parse_args()
    if args.megastep:
        sys.exit(0 if run_megastep(args.out) else 1)
    main(args.dir)
