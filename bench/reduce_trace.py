"""Reduces a JAX profiler trace of the measured window to the numbers the
per-layer metrics read.

A TPU trace holds, per chip, a plane ``/device:TPU:<n>`` with the lines
``XLA Modules`` (one event per jitted program run, ``jit_<name>(<hash>)``),
``XLA Ops`` (one event per HLO instruction run, named by the
instruction's text, ``%<op>.<n> = <shape> <opcode>(...)``) and
``Async XLA Ops`` (async copies, start to done). Control
ops (``while``, ``conditional``, ``call``) span the ops they run, so only
the other ops count as work. A Pallas kernel runs as a custom call
(``ring_gather``, ``ring_write``, ``priority_scatter``; the PER score
kernel shows as ``per_topk``); XLA's ``top_k`` over the priorities runs
as ``sort``.

``load`` turns a trace file into plain event rows; ``reduce`` works on
rows, so the tests can hold it to a small recorded trace.
"""
import collections
import glob
import os
import re

CONTROL = {"while", "conditional", "call"}
_OP = re.compile(r"^%?([^\s=]+)")


def op_name(event_name):
    """'%ring_gather.10 = f32[...] custom-call(...)' -> 'ring_gather.10'"""
    m = _OP.match(event_name)
    return m.group(1) if m else event_name


def op_kind(event_name):
    """The op's name without its instance number: 'ring_gather'."""
    return re.sub(r"\.\d+$", "", op_name(event_name))


def module_kind(event_name):
    """'jit_megastep(1128...)' -> 'jit_megastep'"""
    return event_name.split("(")[0]


def load(trace_dir):
    """-> rows (plane, line, name, start_ns, dur_ns) of the device planes
    and of the host plane, from the newest ``*.xplane.pb`` under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    rows = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not (plane.name.startswith("/device:TPU:")
                or plane.name == "/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                rows.append((plane.name, line.name, e.name,
                             float(e.start_ns), float(e.duration_ns)))
    return rows


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def measure(merged):
    return sum(e - s for s, e in merged)


def device_summary(rows):
    """Rows of one device plane -> its busy time, op and module times."""
    work = []
    ops, counts, modules = (collections.Counter(), collections.Counter(),
                            collections.Counter())
    for _, line, name, start, dur in rows:
        if line == "XLA Modules":
            modules[module_kind(name)] += dur
            continue
        if line not in ("XLA Ops", "Async XLA Ops"):
            continue
        kind = op_kind(name)
        if line == "XLA Ops" and kind not in CONTROL:
            work.append((start, start + dur))
            ops[kind] += dur
            counts[kind] += 1
    busy = union(work)
    return {"busy_ns": measure(busy), "busy": busy, "ops_ns": dict(ops), "op_counts": dict(counts),
            "modules_ns": dict(modules)}


def idle_gaps(busy, host_rows, top=10):
    """The longest gaps between device work, each named by the shortest
    host event that covers the gap's middle (what the host was doing)."""
    gaps = sorted(((e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:])),
                  key=lambda g: g[0] - g[1])[:top]
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [(d, name) for _, _, name, st, d in host_rows
                 if st <= mid <= st + d]
        label = min(cover)[1] if cover else "no host event"
        out.append([f"host: {label}", (e - s) * 1e-9])
    return out


def reduce(rows, chips, window_s):
    """-> {"window_s", "busy_s" (mean over the chips), "devices": [per
    chip: busy_s, ops_s, op_counts, modules_s], "breakdown"} for the
    first ``chips`` TPU planes."""
    planes = collections.defaultdict(list)
    for r in rows:
        planes[r[0]].append(r)
    names = sorted((p for p in planes if p.startswith("/device:TPU:")),
                   key=lambda p: int(p.rsplit(":", 1)[1]))[:chips]
    if len(names) < chips:
        raise ValueError(f"trace has {len(names)} TPU planes, {chips} "
                         f"expected")
    devices = []
    for name in names:
        d = device_summary(planes[name])
        devices.append({
            "busy_s": d["busy_ns"] * 1e-9,
            "ops_s": {k: v * 1e-9 for k, v in d["ops_ns"].items()},
            "op_counts": d["op_counts"],
            "modules_s": {k: v * 1e-9 for k, v in d["modules_ns"].items()},
            "busy": d["busy"]})
    top = sorted(devices[0]["ops_s"].items(), key=lambda kv: -kv[1])[:10]
    breakdown = {"device_ops": [[k, v] for k, v in top],
                 "idle_gaps": idle_gaps(devices[0]["busy"],
                                        planes.get("/host:CPU", []))}
    for d in devices:
        del d["busy"]
    return {"window_s": window_s,
            "busy_s": sum(d["busy_s"] for d in devices) / len(devices),
            "devices": devices, "breakdown": breakdown}
