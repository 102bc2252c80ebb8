"""The trace reduction, on hand-made rows and on a recorded v5e trace."""
import gzip
import json
import os

import pytest

import reduce_trace as rt

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "v5e_per_n3_utd2_one_dispatch.json.gz")
DEV = "/device:TPU:0"


def op(name, start, dur, line="XLA Ops", plane=DEV):
    return (plane, line, name, float(start), float(dur))


def test_names():
    text = ("%ring_gather.10 = f32[8192,128]{1,0:T(8,128)S(1)} "
            "custom-call(s32[1]{0:T(128)} %get-tuple-element.3837)")
    assert rt.op_name(text) == "ring_gather.10"
    assert rt.op_kind(text) == "ring_gather"
    assert rt.op_kind("%copy-start.3 = f32[2]") == "copy-start"
    assert rt.module_kind("jit_megastep(11280219525854664416)") == \
        "jit_megastep"


def test_busy_and_ops():
    rows = [
        op("jit_megastep(1)", 0, 100, line="XLA Modules"),
        op("%while.1 = (s32[]) while(...)", 0, 100),     # spans its body
        op("%ring_gather.2 = f32[8,128] custom-call()", 10, 20),
        op("%fusion.3 = f32[8] fusion()", 25, 15),       # overlaps gather
        op("%copy.4 = f32[2] copy()", 60, 10),
        op("%fusion.5 = f32[8] fusion()", 65, 10),       # overlaps copy
        op("%copy-start.6 = f32[2] copy-start()", 0, 90,
           line="Async XLA Ops"),                        # not work
        op("%sort.7 = (f32[1048576]) sort()", 200, 10, plane="/device:TPU:1"),
    ]
    red = rt.reduce(rows, 1, 1e-6)                       # a 1000 ns window
    d = red["devices"][0]
    # work: [10, 40) and [60, 75): 45 ns
    assert d["busy_s"] == pytest.approx(45e-9)
    assert red["busy_s"] == pytest.approx(45e-9)
    assert d["ops_s"]["ring_gather"] == pytest.approx(20e-9)
    assert d["ops_s"]["fusion"] == pytest.approx(25e-9)
    assert "while" not in d["ops_s"]
    assert d["op_counts"]["ring_gather"] == 1
    assert d["ops_s"]["copy"] == pytest.approx(10e-9)
    assert d["modules_s"]["jit_megastep"] == pytest.approx(100e-9)
    # the gaps between work: [40, 60)
    assert red["breakdown"]["idle_gaps"][0][1] == pytest.approx(20e-9)
    two = rt.reduce(rows, 2, 1e-6)
    assert two["devices"][1]["ops_s"] == {"sort": pytest.approx(10e-9)}
    with pytest.raises(ValueError):
        rt.reduce(rows, 4, 1e-6)


def test_interval_arithmetic():
    assert rt.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert rt.union([(0, 10), (2, 3), (10, 12)]) == [[0, 12]]
    assert rt.measure(rt.union([(0, 10), (5, 20), (30, 31)])) == 21


def recorded():
    with gzip.open(FIXTURE, "rt") as f:
        rec = json.load(f)
    return [tuple(r) for r in rec["rows"]], rec["window_s"]


def test_recorded_v5e_trace():
    """One PER dispatch (sac_hopper.per_n3_utd2) and an eval, traced on a
    TPU v5 lite chip: every kernel and module the metrics read is found
    by the names the trace shows."""
    rows, window_s = recorded()
    red = rt.reduce(rows, 1, window_s)
    d = red["devices"][0]
    for kernel in ("ring_write", "ring_gather", "priority_scatter", "sort",
                   "per_topk"):
        assert d["ops_s"].get(kernel, 0) > 0, kernel
    # one dispatch: 4 ring writes, 4 rounds x 8 updates of the rest
    assert d["op_counts"]["ring_write"] == 4
    assert d["op_counts"]["ring_gather"] == 32
    assert d["op_counts"]["priority_scatter"] == 32
    assert d["op_counts"]["sort"] == d["op_counts"]["per_topk"] == 32
    assert d["modules_s"]["jit_megastep"] > 0
    assert d["modules_s"]["jit_eval_batch"] > 0
    assert 0 < red["busy_s"] <= window_s
    assert len(red["breakdown"]["device_ops"]) == 10
    assert all(s > 0 for _, s in red["breakdown"]["device_ops"])


def test_metric_readers_on_the_recorded_trace():
    """Every per-layer metric of the PER cell reads a number in range
    from the recorded dispatch."""
    from catalog import Catalog
    cat = Catalog()
    cell, cfg, mix, _ = cat.workload("sac_hopper.per_n3_utd2")
    rows, window_s = recorded()
    frames = mix["num_envs"] * mix["chunk_len"] * mix["rounds_per_dispatch"]
    run = {"cell": cell, "config": cfg, "traffic": mix, "chips": 1,
           "device_kind": "TPU v5 lite", "frames": frames,
           "updates": mix["updates_per_round"] * mix["rounds_per_dispatch"],
           "dispatches": 1, "window_s": window_s,
           "trace": rt.reduce(rows, 1, window_s)}
    got = {m["name"]: cat.reader(m["name"])(run)
           for m in cat.metrics("per_layer", cell)}
    assert set(got) == {"device_idle_share", "eval_device_share",
                        "megastep_mfu", "ring_write_roofline",
                        "ring_gather_roofline", "per_select_ms",
                        "priority_scatter_ms"}
    for name in ("megastep_mfu", "ring_write_roofline",
                 "ring_gather_roofline", "eval_device_share"):
        assert 0 < got[name] <= 100, (name, got[name])
    assert 0 <= got["device_idle_share"] < 100
    # sort + score kernel: ~1.15 ms per update on v5e; scatter ~0.19 ms
    assert 0.5 < got["per_select_ms"] < 3
    assert 0.05 < got["priority_scatter_ms"] < 1
