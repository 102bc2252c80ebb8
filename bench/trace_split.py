"""Traces one benchmark cell's window and reads the program's own spans
and scopes (``reduce_spans``) beside the per-layer metrics the harness
prints.

    python3 bench/trace_split.py --workload <cell> --seed <n> [--keep <dir>]

Set-up as the harness's: the trainer from the seed, the warmup, and
dispatches until the ring has wrapped once. Then, over the harness's
traced window (``harness.traced_dispatches``): one window with the
profiler off, one traced, one off again, so the traced window's
frames/s can be set beside the untraced; the trace is reduced by
``reduce_trace`` and ``reduce_spans``. With ``--keep`` the trace stays
under ``<dir>/window``, and a traced window of two dispatches (the
first publishes an eval) is added under ``<dir>/short``, with its rows
in ``<dir>/short.json`` (of the host, the threads that wrote spans).
The last line on standard output is one JSON object. The run needs a
TPU; without one it exits 1.
"""
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import reduce_spans  # noqa: E402
import reduce_trace  # noqa: E402
from catalog import Catalog  # noqa: E402

#: the kept short window: two dispatches, the first publishes an eval
SHORT = 2


def window(tr, dispatches, trace_dir=None):
    """``dispatches`` dispatches through ``train()`` -> (frames, updates,
    seconds, the history's ``eval_blocked_s``), traced into
    ``trace_dir`` where one is given."""
    import jax
    per = tr.cfg.num_envs * tr.cfg.chunk_len * tr.cfg.rounds_per_dispatch
    f0, u0 = tr.total_frames, tr.total_updates
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    hist = tr.train(max_frames=f0 + per * dispatches)
    jax.block_until_ready(tr.state)
    secs = time.perf_counter() - t0
    if trace_dir:
        jax.profiler.stop_trace()
    return tr.total_frames - f0, tr.total_updates - u0, secs, \
        hist.eval_blocked_s


def reduced_run(cat, cell, cfg, mix, device_kind, counts, trace_dir):
    """The harness's ``run`` record of a traced window, with
    ``run["spans"]``, and the per-layer metrics read from it."""
    frames, updates, secs, _ = counts
    per = mix["num_envs"] * mix["chunk_len"] * mix["rounds_per_dispatch"]
    rows = reduce_spans.load(trace_dir)
    run = {"cell": cell, "config": cfg, "traffic": mix, "chips": 1,
           "device_kind": device_kind, "frames": frames, "updates": updates,
           "dispatches": frames // per, "window_s": secs,
           "trace": reduce_trace.reduce([r[:5] for r in rows], 1, secs),
           "spans": reduce_spans.reduce(rows, secs)}
    got = {m["name"]: cat.reader(m["name"])(run)
           for m in cat.metrics("per_layer", cell)}
    got.update(reduce_spans.metrics(run))
    return run, got


def measure(cat, cell, cfg, mix, seed, device, keep=None):
    """Set-up, the three windows and the reductions -> the result."""
    import jax
    from repro.core import SpreezeTrainer

    tr = SpreezeTrainer(harness.program_config(cfg, mix, seed))
    per = mix["num_envs"] * mix["chunk_len"] * mix["rounds_per_dispatch"]
    tr.train(max_frames=1)
    tr.train(max_frames=tr.total_frames
             + per * harness.fill_dispatches(cfg, mix))
    jax.block_until_ready(tr.state)
    n = harness.traced_dispatches(mix)
    out = {"workload": cell["name"], "seed": seed,
           "device": {"platform": device.platform,
                      "kind": device.device_kind},
           "dispatches": n}
    tdir = (os.path.join(keep, "window") if keep
            else tempfile.mkdtemp(prefix="trace_split_"))
    try:
        off = window(tr, n)
        on = window(tr, n, tdir)
        off2 = window(tr, n)
        run, out["metrics"] = reduced_run(cat, cell, cfg, mix,
                                          device.device_kind, on, tdir)
    finally:
        if not keep:
            shutil.rmtree(tdir, ignore_errors=True)
    rate = [c[0] / c[2] for c in (off, on, off2)]
    out["frames_per_s"] = {"off": rate[0], "traced": rate[1],
                           "off_after": rate[2]}
    out["tracing_cost_pct"] = 100.0 * (1 - 2 * rate[1]
                                       / (rate[0] + rate[2]))
    out["window_s"] = on[2]
    # the train thread's time in the publish block, by its own counter;
    # reduce_spans reads the same interval as ``spreeze.publish``
    out["eval_blocked_s"] = on[3]
    out["busy_s"] = run["trace"]["busy_s"]
    out["spans"] = run["spans"]
    out["breakdown"] = run["trace"]["breakdown"]
    if keep:
        kdir = os.path.join(keep, "short")
        frames, updates, secs, _ = window(tr, SHORT, kdir)
        rows = reduce_spans.load(kdir)
        # host threads that wrote no span (the runtime's own) stay out
        spanned = {line for plane, line, name, *_ in rows
                   if plane == reduce_spans.HOST
                   and name.startswith("spreeze.")}
        with open(os.path.join(keep, "short.json"), "w") as f:
            json.dump({"window_s": secs, "frames": frames,
                       "updates": updates,
                       "rows": [r for r in rows
                                if r[0] != reduce_spans.HOST
                                or r[1] in spanned]}, f)
    return out


def main(argv):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--keep", help="directory to keep the traces in")
    args = ap.parse_args(argv)
    cat = Catalog()
    cell, cfg, mix, _ = cat.workload(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cat.root,
                                                           ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = jax.devices()[0]
    if dev.platform != "tpu" or cell["chips"] != 1:
        harness.log(f"trace_split needs one TPU chip; JAX found "
                    f"{dev.platform}, the cell asks for {cell['chips']}")
        return 1
    sys.path.insert(0, os.path.join(cat.root, "src"))
    out = measure(cat, cell, cfg, mix, args.seed, dev, args.keep)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
