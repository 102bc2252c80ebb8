"""The program's own tracing: host spans of the train loop and its
workers (``spreeze.*``, read back from a profiler trace), and the named
scopes of the megastep's stages (op metadata only: the optimized program
is the same op for op)."""
import contextlib
import glob
import os
import re

import jax
import pytest
from _hlo_program import program

from repro.core import SpreezeConfig, SpreezeTrainer
from repro.core import pipeline

TRAIN_SPANS = ("spreeze.dispatch", "spreeze.guard_poll", "spreeze.publish",
               "spreeze.snapshot", "spreeze.barrier")
WORKER_SPANS = ("spreeze.eval", "spreeze.viz", "spreeze.state_write")
STAGES = ("sampler_chunk", "ring_write", "sample", "update", "reprioritise")


def _cfg(**kw):
    base = dict(env_name="pendulum", algo="sac", num_envs=2, batch_size=32,
                chunk_len=4, updates_per_round=2, warmup_frames=32,
                replay_capacity=256, eval_every_rounds=4, eval_episodes=1,
                seed=3)
    base.update(kw)
    return SpreezeConfig(**base)


def _host_spans(trace_dir):
    """-> {host line index: [(name, start_ns, end_ns, stats)]} of the
    ``spreeze.*`` events in the trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    plane = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    out = {}
    for i, line in enumerate(plane.lines):
        spans = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                 for e in line.events if e.name.startswith("spreeze.")]
        if spans:
            out[i] = sorted(spans, key=lambda s: s[1])
    return out


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("path", ["fused", "sync"])
def test_train_writes_nested_spans(path, tmp_path):
    fused = path == "fused"
    rounds = 3 if fused else 1
    cfg = _cfg(rounds_per_dispatch=rounds, sync_mode=not fused,
               **(dict(viz_every_rounds=6, viz_dir=str(tmp_path / "viz"),
                       snapshot_dir=str(tmp_path / "snap"),
                       snapshot_every_rounds=3, snapshot_min_interval_s=0.0)
                  if fused else {}))
    tr = SpreezeTrainer(cfg)
    tr.train(max_frames=1)                     # warmup and compiles
    dispatches = 4
    frames = cfg.num_envs * cfg.chunk_len * rounds
    trace_dir = str(tmp_path / "trace")
    with jax.profiler.trace(trace_dir):
        tr.train(max_frames=tr.total_frames + frames * dispatches)
    lines = _host_spans(trace_dir)

    # the train thread is the one line holding the dispatch spans
    train_line, = [i for i, s in lines.items()
                   if any(n == "spreeze.dispatch" for n, *_ in s)]
    train = lines[train_line]
    by_name = {n: [s for s in train if s[0] == n] for n in TRAIN_SPANS}
    assert {n for n, *_ in train} <= set(TRAIN_SPANS)
    steps = [s[3]["step_num"] for s in by_name["spreeze.dispatch"]]
    assert steps == [rounds * d for d in range(dispatches)]
    assert len(by_name["spreeze.guard_poll"]) == dispatches
    evals = [r for r in steps
             if pipeline._window_hits(r, rounds, cfg.eval_every_rounds)]
    assert len(by_name["spreeze.publish"]) == len(evals) > 0
    barrier, = by_name["spreeze.barrier"]
    # the train thread's spans are siblings: none holds another, each
    # publish follows its dispatch, the barrier closes the window
    for a in train:
        for b in train:
            assert a is b or not _inside(a, b), (a, b)
    for pub in by_name["spreeze.publish"]:
        assert any(d[2] <= pub[1] for d in by_name["spreeze.dispatch"])
    assert barrier[1] >= max(s[2] for s in train if s is not barrier)

    workers = [s for i, ss in lines.items() if i != train_line for s in ss]
    assert {n for n, *_ in workers} <= set(WORKER_SPANS)
    if fused:
        # eval, viz and the bundle write run on worker threads
        assert {n for n, *_ in workers} == set(WORKER_SPANS)
        assert by_name["spreeze.snapshot"]
    else:
        # the sync path evaluates inline, inside the publish span
        assert not workers and not by_name["spreeze.snapshot"]


def _trainer(prioritized):
    return SpreezeTrainer(_cfg(
        env_name="hopper" if prioritized else "pendulum",
        prioritized=prioritized, nstep=3 if prioritized else 1,
        use_pallas=True, eval_every_rounds=0))


def _megastep_args(tr):
    return tr.state, tr.replay, tr.env_states, tr.key


def _op_names(hlo):
    return set(re.findall(r'op_name="([^"]*)"', hlo))


def _has_stage(names, stage):
    pat = re.compile(rf"(?:^|/){stage}/")
    return any(pat.search(n) for n in names)


@pytest.mark.parametrize("prioritized", [False, True])
def test_megastep_metadata_names_each_stage(prioritized):
    tr = _trainer(prioritized)
    names = _op_names(tr._megastep.lower(*_megastep_args(tr)).as_text(
        dialect="hlo", debug_info=True))
    want = STAGES if prioritized else STAGES[:-1]
    for stage in want:
        assert _has_stage(names, stage), stage
    assert _has_stage(names, "reprioritise") == prioritized


@contextlib.contextmanager
def _no_compile_cache():
    """A cache hit would hand back a program compiled with the other
    metadata (the cache key leaves metadata out)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _optimized(prioritized):
    tr = _trainer(prioritized)
    return tr._megastep.lower(*_megastep_args(tr)).compile().as_text()


@pytest.mark.parametrize("prioritized", [False, True])
def test_scopes_leave_the_optimized_program_unchanged(prioritized,
                                                      monkeypatch):
    with _no_compile_cache():
        scoped = _optimized(prioritized)
        monkeypatch.setattr(pipeline, "_scope",
                            lambda name: contextlib.nullcontext())
        plain = _optimized(prioritized)
    assert _has_stage(_op_names(scoped), "update")
    assert not _has_stage(_op_names(plain), "update")
    ops = program(scoped)
    assert "fusion" in ops and ", metadata=" not in ops
    assert ops == program(plain)
