"""Reduces the program's own spans and scopes in a profiler trace of the
measured window, beside what ``reduce_trace`` reads from XLA's names.

The program writes two kinds of marks (``core/pipeline.py``,
``core/runtime.py``):

- host spans, ``jax.profiler`` annotations named ``spreeze.*``: on the
  train thread ``spreeze.dispatch`` (one per megastep dispatch, with its
  first round as ``step_num``), ``spreeze.guard_poll``,
  ``spreeze.publish``, ``spreeze.snapshot`` and ``spreeze.barrier``; on
  the worker threads ``spreeze.eval``, ``spreeze.viz`` and
  ``spreeze.state_write``. Each host thread is a line of the plane
  ``/host:CPU``; the train thread's is the line that holds the
  ``spreeze.dispatch`` spans.
- device scopes, ``jax.named_scope`` around the megastep's stages
  (``STAGES``): each XLA op's ``op_name`` metadata holds its scope path.
  A TPU trace keeps it as the ``tf_op`` stat of the op's event metadata,
  which ``ProfileData`` does not expose (an op event's own stats are its
  device offset and duration); the trace viewer's file that the profiler
  writes beside the XPlane, ``*.trace.json.gz``, carries it in each op
  event's ``args``, with the op's full text as ``long_name``.

From them this module finds:

- whether the host and device planes share a clock, from causality
  anchors: each ``jit_megastep`` program starts after its
  ``spreeze.dispatch`` span begins, and the last one ends before
  ``spreeze.barrier`` ends. The worst violation is ``clock_skew_us``
  (0 when every anchor holds);
- where the device's idle time lies: between megastep programs (the
  host loop's gap), inside them (bubbles between ops, scan steps and
  kernel launches), or at the window's edges, before the first program
  and after the last; the three add up to the idle time;
- each stage's device time, and the share of the megastep's busy time
  that the stages cover;
- the longest idle gaps, each named by the train thread's innermost span
  that covers it (``host: spreeze.publish``) where it lies between
  programs, or by the stage of the op that ends it (``device: update``)
  where it lies inside one; between programs, ``clock not aligned``
  where an anchor fails;
- each span's count and host time, on whichever thread it ran: a worker
  blocked in ``spreeze.eval`` is told apart from the train loop.

A trace of a program without these marks reduces to what it holds: no
stage times and no anchors; ``metrics`` then gives None for what it
cannot read, and nothing here raises for their absence.
"""
import bisect
import collections
import glob
import gzip
import json
import os

from reduce_trace import CONTROL, measure, module_kind, op_kind, union

STAGES = ("sampler_chunk", "ring_write", "sample", "update", "reprioritise")
#: the megastep's ops outside the stages: loop control, key splits, the
#: round metrics, the finite guard
UNSTAGED = "unstaged"
MEGASTEP = "jit_megastep"
DISPATCH, BARRIER = "spreeze.dispatch", "spreeze.barrier"
HOST = "/host:CPU"
#: the chip whose plane is read: every cell that traces runs on one
DEVICE = "/device:TPU:0"
#: the trace viewer's stat that holds an XLA op's ``op_name`` metadata
SCOPE_STAT = "tf_op"


def stage(op_path):
    """'jit(megastep)/while/body/update/jvp(...)/dot_general:' ->
    'update': the innermost stage on the op's scope path, or ''."""
    parts = op_path.rsplit(":", 1)[0].split("/")
    for part in reversed(parts):
        if part in STAGES:
            return part
    return ""


def _newest(trace_dir, pattern):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", pattern),
                             recursive=True), key=os.path.getmtime)
    return paths[-1] if paths else None


def op_paths(trace_dir):
    """{an op's full text (its event name): its ``op_name`` path} from
    the trace viewer's file; empty where there is none."""
    path = _newest(trace_dir, "*.trace.json.gz")
    if path is None:
        return {}
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    return {a["long_name"]: a[SCOPE_STAT] for a in
            (e.get("args") or {} for e in events)
            if SCOPE_STAT in a and "long_name" in a}


def load(trace_dir):
    """-> rows (plane, line, name, start_ns, dur_ns, stage) of the plane
    ``DEVICE`` and of the host plane, from the newest ``*.xplane.pb``
    under ``trace_dir``. A host line is ``<thread name>#<index>``: threads
    share names. ``stage`` is an XLA op's stage, else ''."""
    from jax.profiler import ProfileData
    path = _newest(trace_dir, "*.xplane.pb")
    if path is None:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    scopes = {name: stage(p) for name, p in op_paths(trace_dir).items()}
    rows = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name not in (DEVICE, HOST):
            continue
        for i, line in enumerate(plane.lines):
            name = f"{line.name}#{i}" if plane.name == HOST else line.name
            ops = line.name == "XLA Ops"
            for e in line.events:
                rows.append((plane.name, name, e.name, float(e.start_ns),
                             float(e.duration_ns),
                             scopes.get(e.name, "") if ops else ""))
    return rows


def clip(merged, windows):
    """The parts of the merged intervals that lie in the merged
    ``windows``."""
    out = []
    for ws, we in windows:
        for s, e in merged:
            if s < we and e > ws:
                out.append((max(s, ws), min(e, we)))
    return union(out)


def train_line(rows):
    """The host line that holds the ``spreeze.dispatch`` spans, or
    None."""
    lines = collections.Counter(line for plane, line, name, *_ in rows
                                if plane == HOST and name == DISPATCH)
    return lines.most_common(1)[0][0] if lines else None


def anchors(modules, spans):
    """Causality anchors between the megastep programs and the train
    thread's spans -> (violations in ns, each > 0 where it fails), or
    None where the spans cannot be matched to the programs."""
    starts = sorted(s for s, _, name in spans if name == DISPATCH)
    barrier = [e for _, e, name in spans if name == BARRIER]
    if not starts or len(starts) != len(modules) or len(barrier) != 1:
        return None
    out = [d - m for d, (m, _) in zip(starts, modules)]
    out.append(modules[-1][1] - barrier[0])
    return out


def gaps(busy, modules, ops):
    """Each gap between device work -> (start, end, the stage of the op
    that ends it where the gap lies inside one program, else None)."""
    starts, mstarts = [s for s, *_ in ops], [s for s, _ in modules]
    out = []
    for (_, s), (e, _) in zip(busy, busy[1:]):
        m = bisect.bisect_right(mstarts, s) - 1
        inside = m >= 0 and e <= modules[m][1]
        out.append((s, e, (ops[bisect.bisect_left(starts, e)][2]
                           or UNSTAGED) if inside else None))
    return out


def idle_gaps(gap_list, spans, aligned, top=10):
    """The longest gaps, each with its label."""
    out = []
    for s, e, where in sorted(gap_list, key=lambda g: g[0] - g[1])[:top]:
        if where is not None:
            label = f"device: {where}"
        elif not aligned:
            label = "clock not aligned"
        else:
            mid = (s + e) / 2
            cover = [(se - ss, name) for ss, se, name in spans
                     if ss <= mid <= se]
            label = (f"host: {min(cover)[1]}" if cover
                     else "host: outside spreeze spans")
        out.append([label, (e - s) * 1e-9])
    return out


def reduce(rows, window_s):
    """-> {"clock_skew_us", "clock_aligned", "megasteps", "stages_s",
    "stage_share", "stage_ops_s" (each stage's five largest op kinds; ops
    outside the stages under ``unstaged``), "idle_s":
    {"between_megasteps", "inside_megasteps", "edges"},
    "inside_idle_s" (the gaps inside the programs by the stage of the op
    that ends each), "idle_gaps",
    "spans_s": {span: (count, host seconds)}} for the plane ``DEVICE``
    and the host's threads."""
    modules, ops = [], []
    for plane, line, name, start, dur, scope in rows:
        if plane != DEVICE:
            continue
        if line == "XLA Modules" and module_kind(name) == MEGASTEP:
            modules.append((start, start + dur))
        elif line == "XLA Ops" and op_kind(name) not in CONTROL:
            ops.append((start, start + dur, scope, op_kind(name)))
    modules.sort()
    ops.sort()
    busy = union((s, e) for s, e, *_ in ops)
    tl = train_line(rows)
    spans = sorted((s, s + d, name) for plane, line, name, s, d, _ in rows
                   if plane == HOST and line == tl
                   and name.startswith("spreeze."))
    out = {"megasteps": len(modules), "clock_skew_us": None,
           "clock_aligned": False, "stages_s": {}, "stage_share": None,
           "idle_s": None, "idle_gaps": [], "spans_s": {}}
    for plane, _, name, _, dur, _ in rows:
        if plane == HOST and name.startswith("spreeze."):
            n, secs = out["spans_s"].get(name, (0, 0.0))
            out["spans_s"][name] = (n + 1, secs + dur * 1e-9)
    if not modules:
        return out
    held = anchors(modules, spans)
    if held is not None:
        out["clock_skew_us"] = max(0.0, max(held)) * 1e-3
        out["clock_aligned"] = max(held) <= 0
    kinds = collections.defaultdict(collections.Counter)
    for s, e, scope, kind in ops:
        kinds[scope or UNSTAGED][kind] += (e - s) * 1e-9
        if scope:
            out["stages_s"][scope] = out["stages_s"].get(scope, 0.0) + (
                e - s) * 1e-9
    out["stage_ops_s"] = {k: dict(c.most_common(5))
                          for k, c in kinds.items()}
    mod = union(modules)
    in_mod = clip(busy, mod)
    staged = clip(union((s, e) for s, e, scope, _ in ops if scope), mod)
    if out["stages_s"] and in_mod:
        out["stage_share"] = measure(staged) / measure(in_mod)
    first, last = mod[0][0], mod[-1][1]
    between = [(e0, s1) for (_, e0), (s1, _) in zip(mod, mod[1:])]
    span = last - first
    out["idle_s"] = {
        "between_megasteps": (measure(between)
                              - measure(clip(busy, between))) * 1e-9,
        "inside_megasteps": (measure(mod) - measure(in_mod)) * 1e-9,
        "edges": window_s - (span + measure(busy)
                             - measure(clip(busy, [(first, last)]))) * 1e-9}
    gap_list = gaps(busy, mod, ops)
    inside = collections.Counter()
    for s, e, where in gap_list:
        if where is not None:
            inside[where] += (e - s) * 1e-9
    out["inside_idle_s"] = dict(inside)
    out["idle_gaps"] = idle_gaps(gap_list, spans, out["clock_aligned"])
    return out


def metrics(run):
    """The per-layer numbers of ``run["spans"]`` (a ``reduce`` result),
    with ``run`` as ``harness.run_cell`` builds it; None for each that
    the trace cannot give."""
    sp, mix = run.get("spans") or {}, run["traffic"]
    stages, idle = sp.get("stages_s") or {}, sp.get("idle_s")
    rounds = run["frames"] // (mix["num_envs"] * mix["chunk_len"])
    window = run["trace"]["window_s"]

    def per(stage_name, count):
        secs = stages.get(stage_name)
        return 1e3 * secs / count if secs and count else None

    return {
        "sampler_chunk_ms": per("sampler_chunk", rounds),
        "update_ms": per("update", run["updates"]),
        "host_gap_ms": (1e3 * idle["between_megasteps"] / run["dispatches"]
                        if idle and run["dispatches"] else None),
        "megastep_idle_share": (100.0 * idle["inside_megasteps"] / window
                                if idle else None)}
