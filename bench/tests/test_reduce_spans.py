"""The reduction of the program's own spans and scopes, on hand-made rows
and on a recorded v5e trace of two dispatches."""
import gzip
import json
import os

import pytest

import reduce_spans as rs
import reduce_trace as rt
from catalog import Catalog

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
DEV, HOST = "/device:TPU:0", "/host:CPU"
TRAIN, WORKER = "python3#0", "python3#1"


def dev(name, start, dur, line="XLA Ops", stage=""):
    return (DEV, line, name, float(start), float(dur), stage)


def host(name, start, dur, line=TRAIN):
    return (HOST, line, name, float(start), float(dur), "")


def two_dispatches(shift=0.0):
    """Two megastep programs [100, 300) and [320, 500), an eval program
    between them, and the train thread's spans; ``shift`` moves the
    device plane against the host's."""
    d = [
        dev("jit_megastep(1)", 100, 200, line="XLA Modules"),
        dev("%while.1 = () while()", 100, 200),
        dev("%fusion.2 = f32[8] fusion()", 100, 50, stage="sampler_chunk"),
        dev("%ring_write.3 = f32[8,128] custom-call()", 160, 20,
            stage="ring_write"),
        dev("%ring_gather.4 = f32[8,128] custom-call()", 180, 40,
            stage="sample"),
        dev("%fusion.5 = f32[8] fusion()", 240, 60, stage="update"),
        dev("jit_eval_batch(2)", 305, 10, line="XLA Modules"),
        dev("%fusion.6 = f32[4] fusion()", 305, 10),
        dev("jit_megastep(1)", 320, 180, line="XLA Modules"),
        dev("%fusion.7 = f32[8] fusion()", 320, 90, stage="sampler_chunk"),
        dev("%add.8 = u32[2] add()", 410, 5),
        dev("%fusion.9 = f32[8] fusion()", 430, 70, stage="update"),
    ]
    d = [r[:3] + (r[3] + shift,) + r[4:] for r in d]
    h = [
        host("spreeze.guard_poll", 10, 5),
        host("spreeze.dispatch", 20, 60),
        host("spreeze.publish", 85, 10),
        host("spreeze.guard_poll", 96, 2),
        host("spreeze.dispatch", 300, 15),
        host("spreeze.barrier", 316, 200),
        # the eval worker waits on its result across the gap [315, 320)
        host("spreeze.eval", 305, 30, line=WORKER),
        host("np.asarray(jax.Array)", 310, 20, line=WORKER),
    ]
    return d + h


def recorded():
    """Cell 3, ``sac_hopper.uniform_n3_utd2``: two dispatches, the first
    publishing an eval, traced on a TPU v5 lite chip; rows as ``load``
    gives them, of the host the threads that wrote spans."""
    with gzip.open(os.path.join(
            FIXTURES, "v5e_uniform_n3_utd2_two_dispatches.json.gz"),
            "rt") as f:
        rec = json.load(f)
    return [tuple(r) for r in rec["rows"]], rec


def test_recorded_two_dispatches():
    rows, rec = recorded()
    window_s = rec["window_s"]
    red = rs.reduce(rows, window_s)
    assert red["megasteps"] == 2
    # each program starts after its dispatch span begins, the last ends
    # before the barrier ends
    assert red["clock_aligned"] and red["clock_skew_us"] == 0.0
    counts = {k: n for k, (n, _) in red["spans_s"].items()}
    assert counts == {"spreeze.guard_poll": 2, "spreeze.dispatch": 2,
                      "spreeze.publish": 1, "spreeze.barrier": 1,
                      "spreeze.eval": 1}
    assert set(red["stages_s"]) == set(rs.STAGES) - {"reprioritise"}
    assert red["stage_share"] >= 0.9
    trace = rt.reduce([r[:5] for r in rows], 1, window_s)
    assert sum(red["idle_s"].values()) == pytest.approx(
        window_s - trace["busy_s"])
    for label, _ in red["idle_gaps"]:
        assert label.startswith(("device: ", "host: spreeze.")), label
    # reduce_trace's labels name JAX's own events on any thread
    assert all(not lab.startswith("host: spreeze.")
               for lab, _ in trace["breakdown"]["idle_gaps"])

    cat = Catalog()
    cell, cfg, mix, _ = cat.workload("sac_hopper.uniform_n3_utd2")
    run = {"cell": cell, "config": cfg, "traffic": mix, "chips": 1,
           "device_kind": "TPU v5 lite", "frames": rec["frames"],
           "updates": rec["updates"], "dispatches": 2, "window_s": window_s,
           "trace": trace, "spans": red}
    got = rs.metrics(run)
    # v5e: ~0.48 ms a round in the sampler chunk, ~0.22 ms an update
    assert 0.2 < got["sampler_chunk_ms"] < 1.5
    assert 0.1 < got["update_ms"] < 0.3
    assert 0 < got["host_gap_ms"] < 0.02
    idle = cat.reader("device_idle_share")(run)
    assert 0 < got["megastep_idle_share"] < idle


def test_stage_of_a_scope_path():
    assert rs.stage("jit(megastep)/while/body/closed_call/update/"
                    "jvp(vmap())/dot_general") == "update"
    assert rs.stage("jit(megastep)/while/body/sampler_chunk/while/body/"
                    "sample/mul") == "sample"          # the innermost
    assert rs.stage("jit(megastep)/and") == ""
    assert rs.stage("jit(megastep)/while/body/closed_call/ring_write:") \
        == "ring_write"                                # as the trace has it


def test_split_adds_up_and_stages():
    rows = two_dispatches()
    red = rs.reduce(rows, 600e-9)
    assert red["megasteps"] == 2
    assert red["clock_aligned"] and red["clock_skew_us"] == 0.0
    idle = red["idle_s"]
    # [300, 320): the eval program runs [305, 315)
    assert idle["between_megasteps"] == pytest.approx(10e-9)
    # inside: [150, 160), [220, 240), [415, 430), each by the next op
    assert idle["inside_megasteps"] == pytest.approx(45e-9)
    assert red["inside_idle_s"] == {"ring_write": pytest.approx(10e-9),
                                    "update": pytest.approx(35e-9)}
    busy = rt.measure(rt.union((r[3], r[3] + r[4]) for r in rows
                               if r[1] == "XLA Ops" and "while" not in r[2]))
    assert sum(idle.values()) == pytest.approx(600e-9 - busy * 1e-9)
    assert red["stages_s"] == {"sampler_chunk": pytest.approx(140e-9),
                               "ring_write": pytest.approx(20e-9),
                               "sample": pytest.approx(40e-9),
                               "update": pytest.approx(130e-9)}
    # the megasteps are busy 335 ns, 330 of them under a stage
    assert red["stage_share"] == pytest.approx(330 / 335)
    assert red["stage_ops_s"]["unstaged"]["add"] == pytest.approx(5e-9)
    assert red["spans_s"]["spreeze.dispatch"] == (2, pytest.approx(75e-9))
    assert red["spans_s"]["spreeze.eval"] == (1, pytest.approx(30e-9))


def test_gap_labels_prefer_the_train_thread():
    """A worker's long wait covers the gap between the programs; the
    train thread's own span names it, and a gap inside a program is
    named by the stage of the op that ends it."""
    gaps = rs.reduce(two_dispatches(), 600e-9)["idle_gaps"]
    assert [lab for lab, _ in gaps] == [
        "device: update", "device: update", "device: ring_write",
        "host: spreeze.dispatch", "host: spreeze.barrier"]
    assert [s for _, s in gaps] == pytest.approx(
        [20e-9, 15e-9, 10e-9, 5e-9, 5e-9])
    # reduce_trace names the gap by the shortest host event of any thread
    rows = two_dispatches()
    busy = rt.union((r[3], r[3] + r[4]) for r in rows
                    if r[1] == "XLA Ops" and "while" not in r[2])
    old = rt.idle_gaps(busy, [r[:5] for r in rows if r[0] == HOST])
    assert old[-1][0] == "host: np.asarray(jax.Array)"


def test_failed_anchor_labels_no_host_span():
    """A device plane 90 ns ahead of the host's: each program starts
    before its dispatch span begins, the second by 70 ns."""
    red = rs.reduce(two_dispatches(shift=-90), 600e-9)
    assert not red["clock_aligned"]
    assert red["clock_skew_us"] == pytest.approx(0.07)
    labels = [lab for lab, _ in red["idle_gaps"]]
    assert "clock not aligned" in labels
    assert not any(lab.startswith("host:") for lab in labels)


def test_a_program_without_marks_reads_none():
    """The parent's program: no spans, no stages. The split still reads
    from the device alone; the stage metrics read None."""
    rows = [r[:5] + ("",) for r in two_dispatches() if r[0] == DEV]
    red = rs.reduce(rows, 600e-9)
    assert red["clock_skew_us"] is None and not red["clock_aligned"]
    assert red["stages_s"] == {} and red["stage_share"] is None
    cat = Catalog()
    cell, cfg, mix, _ = cat.workload("sac_hopper.uniform_n3_utd2")
    run = {"traffic": mix, "frames": 2 * 4 * 1024 * 32, "updates": 64,
           "dispatches": 2, "trace": {"window_s": 600e-9}, "spans": red}
    got = rs.metrics(run)
    assert got["sampler_chunk_ms"] is None and got["update_ms"] is None
    assert got["host_gap_ms"] == pytest.approx(1e3 * 10e-9 / 2)
    assert got["megastep_idle_share"] == pytest.approx(100 * 45 / 600)
    assert rs.reduce([], 1.0)["idle_s"] is None
    assert rs.metrics({"traffic": mix, "frames": 0, "updates": 0,
                       "dispatches": 0, "trace": {"window_s": 1.0},
                       "spans": rs.reduce([], 1.0)}) == dict.fromkeys(
        ("sampler_chunk_ms", "update_ms", "host_gap_ms",
         "megastep_idle_share"))


#: the seven per-layer metrics on the recorded one-dispatch PER trace,
#: as the parent program's benchmark read them before the spans and
#: scopes came in
PARENT_READINGS = {
    "device_idle_share": 6.080205921410419,
    "eval_device_share": 0.377589040392931,
    "megastep_mfu": 3.9615487791465855,
    "ring_write_roofline": 2.64631822953392,
    "ring_gather_roofline": 0.2323709492363625,
    "per_select_ms": 1.1508151875000003,
    "priority_scatter_ms": 0.18879309375,
}


def test_existing_metrics_read_as_before_on_the_old_trace():
    cat = Catalog()
    cell, cfg, mix, _ = cat.workload("sac_hopper.per_n3_utd2")
    with gzip.open(os.path.join(
            FIXTURES, "v5e_per_n3_utd2_one_dispatch.json.gz"), "rt") as f:
        rec = json.load(f)
    rows, window_s = [tuple(r) for r in rec["rows"]], rec["window_s"]
    frames = mix["num_envs"] * mix["chunk_len"] * mix["rounds_per_dispatch"]
    run = {"cell": cell, "config": cfg, "traffic": mix, "chips": 1,
           "device_kind": "TPU v5 lite", "frames": frames,
           "updates": mix["updates_per_round"] * mix["rounds_per_dispatch"],
           "dispatches": 1, "window_s": window_s,
           "trace": rt.reduce(rows, 1, window_s)}
    got = {m["name"]: cat.reader(m["name"])(run)
           for m in cat.metrics("per_layer", cell)}
    assert got == PARENT_READINGS
