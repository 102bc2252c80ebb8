"""Group-local PER selection: ``per_topk`` (score pass + threshold top-k)
vs the dense oracle (partial fill, ties, signed zeros, ring-wrap layouts,
window edges, k > live rows, k == rows), the key order against ``top_k``,
the two-phase candidate merge, cross-mode/cross-layout determinism of
PER draws, and the compiled-megastep probes (trace counts + no
capacity-sized collective)."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.sharding import trainer_rules, use_rules
from repro.kernels import ops as kops
from repro.kernels import replay_ops as rops
from repro.kernels.ops import use_pallas
from repro.launch.mesh import make_ac_mesh
from repro.replay import buffer as rb
from repro.replay import prioritized as per


def _check_selection(got, want):
    """Scores bit-exact; indices exact wherever the score is finite
    (-inf slots carry IDX_SENTINEL in the kernel — unspecified, and
    never dereferenced: ``sample`` cycles the live draws)."""
    v, i, p = got
    vr, ir, pr = want
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vr))
    fin = np.isfinite(np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(i)[fin], np.asarray(ir)[fin])
    assert (np.asarray(i)[~fin] == rops.IDX_SENTINEL).all()
    np.testing.assert_array_equal(np.asarray(p)[fin], np.asarray(pr)[fin])


def _window(cap, live, k, scores):
    """A (cap,)-row priority window with ``live`` rows scattered through
    it, its Gumbel noise and alpha, shaped so the scores are:
    ``uniform`` — continuous; ``coarse`` — on a grid of 1/2, so runs of
    ties straddle the k-th place; ``signed_zero`` — +0.0, -0.0 (alpha 0
    times the log of a priority below 1) and a few positives;
    ``negative`` — all below zero."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(cap + live + k), 3)
    u = jax.random.uniform(k1, (cap,))
    g = jax.random.gumbel(k3, (cap,))
    alpha = 0.6
    if scores == "coarse":
        u, g = jnp.ones((cap,)), jnp.round(g * 2) / 2
    elif scores == "signed_zero":
        alpha, slot = 0.0, jnp.arange(cap)
        u = jnp.where(slot % 2 == 0, 0.5, 2.0)
        g = jnp.where(slot % 3 == 0, -0.0, jnp.where(slot % 7 == 1, 1.0, 0.0))
    elif scores == "negative":
        g = -jnp.abs(g) - 1.0
    else:
        u = u + 0.01
    pri = jnp.where(jnp.arange(cap) < live, u, 0.0)
    return pri[jax.random.permutation(k2, cap)], g, alpha


@pytest.mark.parametrize("cap,live,k,scores", [
    pytest.param(512, 512, 64, "uniform", id="512-512-64"),   # full pool
    pytest.param(512, 100, 64, "uniform", id="512-100-64"),   # partial fill
    pytest.param(300, 7, 32, "uniform", id="300-7-32"),       # k > live rows
    pytest.param(4096, 3, 16, "uniform", id="4096-3-16"),     # mostly empty
    (2048, 2048, 100, "coarse"),      # ties straddle the k-th place
    (1000, 600, 800, "coarse"),       # ties, then the -inf tail
    (4096, 4096, 1500, "signed_zero"),  # k-th place among the +0.0s
    (4096, 4096, 3600, "signed_zero"),  # ... among the -0.0s below them
    (512, 512, 512, "uniform"),       # k == rows
    (640, 77, 640, "coarse"),         # k == rows, mostly -inf
    (1024, 1024, 100, "negative"),    # every score below zero
    (2000, 1500, 1800, "uniform"),    # fewer live rows than k
    (16384, 16384, 128, "uniform"),   # the PER cell's rows / k ratio
    (17000, 16000, 300, "uniform"),   # 136 lane rows: a ragged last chunk
])
def test_per_topk_matches_dense_oracle(cap, live, k, scores):
    pri, g, alpha = _window(cap, live, k, scores)
    _check_selection(rops.per_topk(pri, g, alpha, k, block=128),
                     rops.per_topk_ref(pri, g, alpha, k))


def test_order_keys_follow_top_k():
    """The selection's int32 keys order floats as ``lax.top_k`` does:
    -inf lowest, -0.0 below +0.0, subnormals in place. Sorting by key
    (descending, stable) must give top_k's permutation."""
    special = np.array([-np.inf, -3.4e38, -1.0, -1e-40, -0.0, 0.0, 1e-40,
                        1.0, 3.4e38, np.inf], np.float32)
    x = np.random.default_rng(0).permutation(np.repeat(special, 7))
    keys = np.asarray(rops._order_keys(jnp.asarray(x)))
    _, want = jax.lax.top_k(jnp.asarray(x), x.size)
    np.testing.assert_array_equal(np.argsort(-keys.astype(np.int64),
                                             kind="stable"),
                                  np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(rops._key_scores(jnp.asarray(keys))).view(np.int32),
        x.view(np.int32))


def test_per_topk_ring_wrap_layout():
    """Live mass hugging both ends of the ring (a wrapped write: newest
    rows at the front, oldest at the back, empty middle) — block edges
    and the live mask must not lose either end."""
    cap, k = 512, 48
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    u = jax.random.uniform(k1, (cap,)) + 0.01
    slot = jnp.arange(cap)
    pri = jnp.where((slot < 30) | (slot >= cap - 20), u, 0.0)
    g = jax.random.gumbel(k2, (cap,))
    _check_selection(rops.per_topk(pri, g, 0.6, k, block=128),
                     rops.per_topk_ref(pri, g, 0.6, k))


def test_per_topk_window_merge_equals_global():
    """The layout-invariance identity at kernel level: 4 window-local
    top-k's (global indices via window_start) merged in fixed window
    order == the dense global top-k."""
    cap, k, G = 512, 48, 4
    pri = jnp.where(jax.random.uniform(jax.random.PRNGKey(5), (cap,)) > 0.6,
                    jax.random.uniform(jax.random.PRNGKey(6), (cap,)) + 0.01,
                    0.0)
    g = jax.random.gumbel(jax.random.PRNGKey(7), (cap,))
    rows = cap // G
    cand = [rops.per_topk(pri[lo:lo + rows], g[lo:lo + rows], 0.6, k,
                          window_start=lo, block=128)
            for lo in range(0, cap, rows)]
    merged = rops.merge_topk_candidates(
        *(jnp.concatenate([c[j] for c in cand]) for j in range(3)), k)
    _check_selection(merged, rops.per_topk_ref(pri, g, 0.6, k))


def test_per_megastep_selects_by_threshold():
    """A single-device PER megastep with the kernels on traces the
    threshold selection, not a dense top-k over the window."""
    from repro.core import SpreezeConfig, SpreezeTrainer
    cfg = SpreezeConfig(env_name="pendulum", algo="sac", num_envs=2,
                        batch_size=32, chunk_len=4, updates_per_round=2,
                        warmup_frames=32, replay_capacity=4096,
                        eval_every_rounds=10**9, seed=3,
                        rounds_per_dispatch=2, prioritized=True,
                        use_pallas=True)
    rops.reset_trace_counts()
    tr = SpreezeTrainer(cfg)
    tr._megastep.lower(tr.state, tr.replay, tr.env_states, tr.key)
    assert rops.TRACE_COUNTS["per_topk_threshold"] > 0, rops.TRACE_COUNTS


def test_per_topk_rejects_k_beyond_window():
    with pytest.raises(ValueError, match="window"):
        rops.per_topk(jnp.ones((8,)), jnp.zeros((8,)), 0.6, 9)


def _rows(n, base=0.0):
    return {"obs": jnp.zeros((n, 2)), "act": jnp.zeros((n, 1)),
            "rew": jnp.arange(n, dtype=jnp.float32) + base,
            "next_obs": jnp.zeros((n, 2)), "done": jnp.zeros((n,))}


def test_pallas_sample_cycles_live_rows_never_unwritten():
    """k > live rows through the KERNEL path: the -inf tail's sentinel
    indices must never surface — surplus draws cycle the live draws
    (the PR-3 unwritten-row bug class, locked for per_topk)."""
    st_ = per.init_prioritized(128, rb.specs_for_env(2, 1))
    st_ = per.add_batch(st_, _rows(3))
    with use_pallas():
        for seed in range(20):
            _, idx, w = per.sample(st_, jax.random.PRNGKey(seed), 8)
            arr = np.asarray(idx)
            assert (arr < 3).all(), (seed, arr)
            assert set(arr.tolist()) == {0, 1, 2}
            np.testing.assert_array_equal(arr[3:6], arr[:3])
            assert np.isfinite(np.asarray(w)).all()


def _draws(mesh_shape=None, placement="ac", pallas=True, cap=64, bs=8):
    """One PER draw from an identically-constructed pool under the given
    (mesh, placement, pallas) context."""
    ctx = contextlib.ExitStack()
    if pallas:
        ctx.enter_context(use_pallas())
    if mesh_shape is not None:
        n = mesh_shape[0] * mesh_shape[1]
        mesh = make_ac_mesh(*mesh_shape)
        ctx.enter_context(use_rules(trainer_rules(mesh, placement)))
    with ctx:
        st = per.init_prioritized(cap, rb.specs_for_env(2, 1))
        st = per.add_batch(st, _rows(24))
        st = per.update_priorities(st, jnp.arange(8), jnp.arange(1.0, 9.0))
        b, i, w = per.sample(st, jax.random.PRNGKey(7), bs)
    return (np.asarray(i), np.asarray(w),
            {k: np.asarray(v) for k, v in b.items()})


def _assert_same_draws(ref, got, what):
    np.testing.assert_array_equal(ref[0], got[0], err_msg=str(what))
    np.testing.assert_allclose(ref[1], got[1], rtol=1e-6)
    for k in ref[2]:
        np.testing.assert_allclose(ref[2][k], got[2][k])


def test_cross_mode_draws_identical_single_device():
    """jnp oracle == fused kernel == (1,1)-mesh shard_map two-phase:
    the same pool + key draws the same batch in every mode."""
    ref = _draws(pallas=False)
    _assert_same_draws(ref, _draws(), "pallas")
    _assert_same_draws(ref, _draws(mesh_shape=(1, 1)), "shard(1,1)")


def test_cross_layout_draws_identical_multidevice():
    """The PR-4 lock-in: (1,1), (1,8) and (2,4) meshes (and the dp
    placement's tuple batch axes) draw bit-identical PER batches —
    group-local selection + the fixed-order candidate merge is the
    dense top-k, and partitionable threefry keeps the Gumbel noise
    layout-invariant."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices (sharded CI job)")
    ref = _draws(pallas=False)
    for shape, placement in [((1, 1), "ac"), ((1, 8), "ac"),
                             ((2, 4), "ac"), ((2, 4), "dp")]:
        _assert_same_draws(ref, _draws(mesh_shape=shape,
                                       placement=placement),
                           (shape, placement))


def test_per_select_mode_dispatch():
    """shard only when kernels on + active batch rules + each group's
    shard holds >= k rows; pallas single-device otherwise; jnp fallback
    when the candidate count can't be covered."""
    assert rb._per_select_mode(64, 8) == "jnp"
    with use_pallas():
        assert rb._per_select_mode(64, 8) == "pallas"
        mesh = make_ac_mesh(1, 1)
        with use_rules(trainer_rules(mesh, "ac")):
            assert rb._per_select_mode(64, 8) == "shard"
            assert rb._per_select_mode(64, 64) == "shard"
            assert rb._per_select_mode(64, 65) == "jnp"  # k > shard rows


def test_per_select_mode_group_shard_too_small():
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices for a batch axis of size 2")
    mesh = make_ac_mesh(1, 2)
    with use_pallas(), use_rules(trainer_rules(mesh, "ac")):
        assert rb._per_select_mode(64, 32) == "shard"
        assert rb._per_select_mode(64, 33) == "jnp"   # 33 > 64 // 2


def test_mesh_pallas_per_rejects_undersized_group_shard():
    """The Pallas opt-in forbids PER configs whose group shards cannot
    emit batch_size candidates (the select would silently fall back to
    the global jnp top_k)."""
    from repro.core import SpreezeConfig, SpreezeTrainer
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices for a batch axis of size 2")
    mesh = make_ac_mesh(1, 2)
    with pytest.raises(ValueError, match="group-local"):
        SpreezeTrainer(SpreezeConfig(
            env_name="pendulum", algo="sac", num_envs=2, batch_size=64,
            chunk_len=4, warmup_frames=64, replay_capacity=64,
            prioritized=True, mesh=mesh, use_pallas=True))


def test_per_megastep_traces_per_topk_no_capacity_collective():
    """The compiled mesh PER megastep must contain the shard_map
    ``per_topk`` path (trace-count probe, as PR 3's ring-kernel probes)
    and no collective whose result is capacity-sized — the only PER
    traffic allowed across groups is the (groups * batch,) candidate
    merge (the full delta assertion runs in benchmarks/roofline.py)."""
    from repro.core import SpreezeConfig, SpreezeTrainer
    from repro.launch.analysis import collective_result_shapes

    mesh = make_ac_mesh(1, 1)
    # the ring gather's reduce-scatter is (batch, record lanes): the
    # capacity must exceed batch * RECORD_LANES for an element count to
    # tell batch-sized traffic from capacity-sized traffic
    cap = 8192
    assert cap > 32 * rb.RECORD_LANES
    cfg = SpreezeConfig(env_name="pendulum", algo="sac", num_envs=2,
                        batch_size=32, chunk_len=4, updates_per_round=2,
                        warmup_frames=32, replay_capacity=cap,
                        eval_every_rounds=10**9, seed=3,
                        rounds_per_dispatch=2, mesh=mesh,
                        prioritized=True, use_pallas=True)
    rops.reset_trace_counts()
    tr = SpreezeTrainer(cfg)
    compiled = tr._megastep.lower(tr.state, tr.replay, tr.env_states,
                                  tr.key).compile()
    assert rops.TRACE_COUNTS["shard:per_topk"] > 0, rops.TRACE_COUNTS
    assert rops.TRACE_COUNTS["per_topk"] > 0, rops.TRACE_COUNTS
    for kind, dims in collective_result_shapes(compiled.as_text()):
        n = int(np.prod(dims)) if dims else 1
        assert n < cap, (kind, dims)
