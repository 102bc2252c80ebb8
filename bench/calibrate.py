"""Readings that the correctness limits of a cell are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--out results.jsonl]

For each of ``--seeds``: the program's warmup and first three
dispatches, read against the plain reference (the lower readings). For
each of ``--control-seeds``: the reference computed in bfloat16 (the
control) and the reference with each planted fault the cell can have
(half of each batch left out; under prioritized replay also drawn rows
left at their priorities, importance weights of 1, uniform draws), each
put in the program's place (the upper readings). Needs the cell's chips,
as a run does. One JSON line per reading.
"""
import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
from catalog import Catalog  # noqa: E402


def readings_for(cat, name, seeds, control_seeds, emit):
    import jax.numpy as jnp

    import compare
    from repro.core import SpreezeTrainer
    from reference import pipeline
    cell, cfg, mix, _ = cat.workload(name)
    for seed in seeds:
        tr = SpreezeTrainer(harness.program_config(cfg, mix, seed))
        prog = harness.follow_program(tr, harness.FOLLOWED)
        del tr
        gc.collect()
        ref = pipeline.follow(cfg, mix, seed, harness.FOLLOWED)
        emit({"workload": name, "seed": seed, "side": "program",
              **compare.readings(prog, ref),
              "detail": compare.detail(prog, ref)})
    faults = pipeline.FAULTS if mix["prioritized"] else ("half_batch",)
    for seed in control_seeds:
        ref = pipeline.follow(cfg, mix, seed, harness.FOLLOWED)
        sides = [("control_bf16", {"dtype": jnp.bfloat16})] + [
            (f"fault_{f}", {"fault": f}) for f in faults]
        for side, kw in sides:
            got = pipeline.follow(cfg, mix, seed, harness.FOLLOWED, **kw)
            emit({"workload": name, "seed": seed, "side": side,
                  **compare.readings(got, ref),
                  "detail": compare.detail(got, ref)})


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args()
    cat = Catalog()
    cell = cat.workload(args.workload)[0]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cat.root,
                                                           ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"calibrate: {args.workload} needs {cell['chips']} TPU "
              f"chip(s)", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(cat.root, "src"))
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    seeds = lambda s: [int(x) for x in s.split(",") if x]
    try:
        readings_for(cat, args.workload, seeds(args.seeds),
                     seeds(args.control_seeds), emit)
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
