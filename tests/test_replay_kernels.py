"""Pallas replay-ring kernels vs their jnp oracles (interpret mode):
blocked write/gather incl. wraparound, tail blocks, and shard windows;
the PER score/scatter kernels; the shard_map wrappers on a trivial and a
multi-device ('ac','batch') mesh; and the trace-time probe proving the
mesh-native megastep contains the Pallas path (no silent jnp fallback)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.sharding import (standard_rules, trainer_rules,
                                        use_rules)
from repro.kernels import ops as kops
from repro.kernels import replay_ops as rops
from repro.kernels.ops import use_pallas
from repro.launch.mesh import make_ac_mesh, make_debug_mesh
from repro.replay import buffer as rb
from repro.replay import prioritized as per


@pytest.mark.parametrize("cap,n,ptr", [
    (8, 3, 0),        # plain append
    (8, 6, 5),        # wraps past capacity
    (8, 8, 7),        # full-capacity write, wraps
    (16, 5, 13),      # wraps by a few rows
    (256, 100, 200),  # multi-block with wrap + partial tail
])
@pytest.mark.parametrize("row", [(), (3,), (2, 2)])
def test_ring_write_matches_oracle(cap, n, ptr, row):
    k1, k2 = jax.random.split(jax.random.PRNGKey(cap * n + ptr))
    data = jax.random.normal(k1, (cap,) + row)
    batch = jax.random.normal(k2, (n,) + row)
    out = rops.ring_write(data, batch, jnp.asarray(ptr, jnp.int32))
    want = rops.ring_write_ref(data, batch, ptr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("block_rows", [1, 3, 8])
def test_ring_write_blocked_edges(block_rows):
    """Small blocks force the full fast/slow/skip predicate matrix:
    interior blocks take the single-DMA fast path, the wrap block and
    the partial tail fall back to row DMAs."""
    cap, n, ptr = 32, 21, 25
    data = jax.random.normal(jax.random.PRNGKey(0), (cap, 4))
    batch = jax.random.normal(jax.random.PRNGKey(1), (n, 4))
    out = rops.ring_write(data, batch, ptr, block_rows=block_rows)
    want = rops.ring_write_ref(data, batch, ptr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want))


def test_ring_write_window_keeps_only_local_rows():
    """The shard window: a 32-slot ring split into 4 windows of 8; each
    window's kernel call keeps exactly the rows landing in its slots."""
    cap, n, ptr = 32, 12, 28        # write wraps 28..39 % 32
    full = jax.random.normal(jax.random.PRNGKey(2), (cap, 3))
    batch = jax.random.normal(jax.random.PRNGKey(3), (n, 3))
    want = rops.ring_write_ref(full, batch, ptr)
    for g in range(4):
        lo = g * 8
        shard_in = full[lo:lo + 8]
        out = rops.ring_write(shard_in, batch, ptr, capacity=cap,
                              window_start=lo, block_rows=4)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(want[lo:lo + 8]))


def test_ring_write_rejects_oversized_batch():
    with pytest.raises(ValueError):
        rops.ring_write(jnp.zeros((4, 2)), jnp.zeros((5, 2)), 0)
    with pytest.raises(ValueError):
        rops.ring_write_rowloop(jnp.zeros((4, 2)), jnp.zeros((5, 2)), 0)


@pytest.mark.parametrize("row", [(), (3,), (2, 2)])
def test_ring_gather_matches_oracle(row):
    data = jax.random.normal(jax.random.PRNGKey(0), (16,) + row)
    idx = jnp.asarray([0, 15, 3, 3, 7, 1], jnp.int32)   # repeats allowed
    out = rops.ring_gather(data, idx)
    want = rops.ring_gather_ref(data, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("block_rows", [1, 4, 7])
def test_ring_gather_blocked_and_windowed(block_rows):
    data = jax.random.normal(jax.random.PRNGKey(4), (24, 5))
    idx = jax.random.randint(jax.random.PRNGKey(5), (13,), 0, 24)
    out = rops.ring_gather(data, idx, block_rows=block_rows)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.take(data, idx, axis=0)))
    # window [8, 16): out-of-window rows come back zeroed
    outw = rops.ring_gather(data[8:16], idx, window_start=8,
                            block_rows=block_rows)
    want = rops.ring_gather_ref(data[8:16], idx, window_start=8)
    np.testing.assert_allclose(np.asarray(outw), np.asarray(want))


def test_rowloop_kernels_match_blocked():
    """The PR-1 row-loop kernels stay alive as the bench baseline; they
    must agree with the blocked kernels everywhere they overlap."""
    data = jax.random.normal(jax.random.PRNGKey(6), (16, 3))
    batch = jax.random.normal(jax.random.PRNGKey(7), (10, 3))
    np.testing.assert_allclose(
        np.asarray(rops.ring_write_rowloop(data, batch, 11)),
        np.asarray(rops.ring_write(data, batch, 11, block_rows=4)))
    idx = jnp.asarray([2, 2, 15, 0, 9], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(rops.ring_gather_rowloop(data, idx)),
        np.asarray(rops.ring_gather(data, idx, block_rows=2)))


def test_per_scores_matches_oracle():
    pri = jnp.asarray([0.0, 1.0, 0.5, 0.0, 3.0, 2.0, 0.0, 0.25])
    g = jax.random.gumbel(jax.random.PRNGKey(8), pri.shape)
    out = rops.per_scores(pri, g, 0.6, block=128)
    want = rops.per_scores_ref(pri, g, 0.6)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    # empty slots are a true -inf even after adding finite noise
    assert np.isneginf(np.asarray(out)[np.asarray(pri) == 0.0]).all()


def test_priority_scatter_matches_oracle_incl_window():
    pri = jnp.linspace(0.1, 1.0, 12)
    idx = jnp.asarray([3, 7, 0, 11], jnp.int32)
    vals = jnp.asarray([9.0, 8.0, 7.0, 6.0])
    out = rops.priority_scatter(pri, idx, vals)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(pri.at[idx].set(vals)))
    # window [4, 8): only the idx==7 update lands, shifted to slot 3
    outw = rops.priority_scatter(pri[4:8], idx, vals, window_start=4)
    np.testing.assert_allclose(
        np.asarray(outw),
        np.asarray(rops.priority_scatter_ref(pri[4:8], idx, vals,
                                             window_start=4)))


@pytest.mark.parametrize("rows,window_start", [
    (4096, 0),        # 4 blocks of (8, 128), lane-dense, in place
    (3000, 1000),     # ragged: padded to whole blocks, windowed
])
def test_priority_scatter_multiblock(rows, window_start):
    """Updates spread over several HBM blocks, plus same-row neighbours
    (one 128-lane row, read-modify-written in turn) and out-of-window
    indices."""
    pri = jax.random.uniform(jax.random.PRNGKey(9), (rows,))
    lo, hi = window_start, window_start + rows
    idx = jnp.asarray([lo, lo + 1, lo + 127, lo + 128, lo + 1030,
                       hi - 1, hi, lo - 1, lo + 2050], jnp.int32)
    vals = jnp.arange(1.0, 10.0)
    out = rops.priority_scatter(pri, idx, vals, window_start=window_start,
                                block=1024)
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(rops.priority_scatter_ref(pri, idx, vals,
                                             window_start=window_start)))


# --------------------------------------------------------------------------- #
# shard_map wrappers + dispatch
# --------------------------------------------------------------------------- #

def _ac_mesh():
    return make_ac_mesh(1, 1)


def test_sharded_wrappers_match_oracles_on_trivial_mesh():
    """The (1,1) mesh exercises the whole shard_map path (windows,
    psum_scatter combine) on any device count."""
    rules = trainer_rules(_ac_mesh(), "ac")
    data = jax.random.normal(jax.random.PRNGKey(9), (16, 3))
    batch = jax.random.normal(jax.random.PRNGKey(10), (6, 3))
    out = jax.jit(lambda d, b: kops.ring_write_sharded(d, b, 13, rules))(
        data, batch)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(rops.ring_write_ref(data, batch,
                                                              13)))
    idx = jnp.asarray([0, 5, 5, 12, 3, 15, 9, 1], jnp.int32)
    out = jax.jit(lambda d, i: kops.ring_gather_sharded(d, i, rules))(
        data, idx)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.take(data, idx, axis=0)))
    pri = jnp.abs(data[:, 0])
    g = jax.random.gumbel(jax.random.PRNGKey(11), pri.shape)
    out = jax.jit(lambda p, n: kops.per_scores_sharded(p, n, 0.6, rules))(
        pri, g)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(rops.per_scores_ref(pri, g,
                                                                 0.6)))
    out = jax.jit(lambda p: kops.priority_scatter_sharded(
        p, idx[:3], jnp.asarray([5.0, 6.0, 7.0]), rules))(pri)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(pri.at[idx[:3]].set(jnp.asarray([5.0, 6.0, 7.0]))))


def test_sharded_wrappers_match_oracles_multidevice():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices (sharded CI job)")
    for placement in ("ac", "dp"):    # dp: rows over BOTH mesh axes
        rules = trainer_rules(make_ac_mesh(2, 4), placement)
        data = jax.random.normal(jax.random.PRNGKey(12), (64, 3))
        batch = jax.random.normal(jax.random.PRNGKey(13), (24, 3))
        out = jax.jit(lambda d, b: kops.ring_write_sharded(
            d, b, 50, rules))(data, batch)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(rops.ring_write_ref(data, batch,
                                                            50)))
        idx = jax.random.randint(jax.random.PRNGKey(14), (16,), 0, 64)
        out = jax.jit(lambda d, i: kops.ring_gather_sharded(
            d, i, rules))(data, idx)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(jnp.take(data, idx, axis=0)))


def test_ring_mode_dispatch():
    """pallas: kernels on, no rules; shard: active ('ac','batch') rules
    with divisible rows; jnp: kernels off, no batch axis, or indivisible
    rows (psum_scatter can't split the output)."""
    assert rb._ring_mode(16) == "jnp"
    with use_pallas():
        assert rb._ring_mode(16) == "pallas"
        with use_rules(trainer_rules(_ac_mesh(), "ac")):
            assert rb._ring_mode(16) == "shard"
            assert rb._ring_mode(16, 8) == "shard"
        mesh_dm = make_debug_mesh(1, 1)
        with use_rules(standard_rules(mesh_dm)):
            # a ("data","model") mesh still maps batch -> ("data",):
            # the ring shards over it like any batch axis
            assert rb._ring_mode(16) == "shard"
        from repro.distributed.sharding import MeshRules
        with use_rules(MeshRules(mesh=mesh_dm)):
            # active rules with NO batch mapping: nothing to shard over
            assert rb._ring_mode(16) == "jnp"
    assert rb._ring_mode(16) == "jnp"


def test_ring_mode_indivisible_rows_fall_back():
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices for a batch axis of size 2")
    mesh = make_ac_mesh(1, 2)
    with use_pallas(), use_rules(trainer_rules(mesh, "ac")):
        assert rb._ring_mode(15) == "jnp"        # cap % groups != 0
        assert rb._ring_mode(16, 7) == "jnp"     # bsz % groups != 0
        assert rb._ring_mode(16, 8) == "shard"


# --------------------------------------------------------------------------- #
# buffer / PER integration on the use_pallas switch
# --------------------------------------------------------------------------- #

def _rows(n, base=0.0):
    return {"obs": jnp.full((n, 2), base),
            "act": jnp.full((n, 1), base + 0.5),
            "rew": jnp.arange(n, dtype=jnp.float32) + base,
            "next_obs": jnp.full((n, 2), base + 1),
            "done": jnp.zeros((n,))}


def test_buffer_pallas_path_matches_jnp():
    specs = rb.specs_for_env(2, 1)
    st_j, st_p = rb.init_replay(8, specs), rb.init_replay(8, specs)
    st_j = rb.add_batch(rb.add_batch(st_j, _rows(6)), _rows(5, base=100))
    with use_pallas():
        st_p = rb.add_batch(rb.add_batch(st_p, _rows(6)),
                            _rows(5, base=100))
    assert int(st_j.ptr) == int(st_p.ptr)
    assert int(st_j.size) == int(st_p.size)
    for k in st_j.data:
        np.testing.assert_allclose(np.asarray(st_j.data[k]),
                                   np.asarray(st_p.data[k]))
    key = jax.random.PRNGKey(1)
    out_j = rb.sample(st_j, key, 16)
    with use_pallas():
        out_p = rb.sample(st_p, key, 16)
    for k in out_j:
        np.testing.assert_allclose(np.asarray(out_j[k]),
                                   np.asarray(out_p[k]))


def test_add_batch_jit_retraces_on_pallas_toggle():
    """The donated jit wrapper is keyed on the trace-time context
    (use_pallas switch + mesh rules), so flipping the switch after a
    first trace must not reuse the cached path."""
    rb._add_batch_jit.cache_clear()   # other tests may hold mesh keys
    st = rb.add_batch_jit(rb.init_replay(8, rb.specs_for_env(2, 1)),
                          _rows(3))
    with use_pallas():
        st = rb.add_batch_jit(st, _rows(3, base=10))
    # each switch state holds its own cache entry
    assert rb._add_batch_jit.cache_info().currsize == 2
    assert int(st.size) == 6


def test_prioritized_pallas_path_matches_jnp():
    specs = rb.specs_for_env(2, 1)
    st_j, st_p = per.init_prioritized(8, specs), per.init_prioritized(8, specs)
    st_j = per.add_batch(per.add_batch(st_j, _rows(6)), _rows(5, base=50))
    with use_pallas():
        st_p = per.add_batch(per.add_batch(st_p, _rows(6)),
                             _rows(5, base=50))
    np.testing.assert_allclose(np.asarray(st_j.priorities),
                               np.asarray(st_p.priorities))
    key = jax.random.PRNGKey(2)
    b_j, i_j, w_j = per.sample(st_j, key, 4)
    with use_pallas():
        b_p, i_p, w_p = per.sample(st_p, key, 4)
    np.testing.assert_array_equal(np.asarray(i_j), np.asarray(i_p))
    np.testing.assert_allclose(np.asarray(w_j), np.asarray(w_p))
    for k in b_j:
        np.testing.assert_allclose(np.asarray(b_j[k]), np.asarray(b_p[k]))
    # the re-prioritization scatter kernel agrees with the jnp form
    st_j2 = per.update_priorities(st_j, i_j, jnp.arange(1.0, 5.0))
    with use_pallas():
        st_p2 = per.update_priorities(st_p, i_p, jnp.arange(1.0, 5.0))
    np.testing.assert_allclose(np.asarray(st_j2.priorities),
                               np.asarray(st_p2.priorities))


# --------------------------------------------------------------------------- #
# trace-time probe: the mesh-native megastep really contains Pallas
# --------------------------------------------------------------------------- #

def test_mesh_megastep_executes_shard_map_kernels():
    """With cfg.mesh + cfg.use_pallas the compiled megastep must trace
    the shard_map ring kernels (counters prove no silent jnp fallback)
    and match the jnp-path mesh trainer's math."""
    from repro.core import SpreezeConfig, SpreezeTrainer

    def cfg(**kw):
        base = dict(env_name="pendulum", algo="sac", num_envs=2,
                    batch_size=32, chunk_len=4, updates_per_round=2,
                    warmup_frames=32, replay_capacity=256,
                    eval_every_rounds=10**9, seed=3,
                    rounds_per_dispatch=2)
        base.update(kw)
        return SpreezeConfig(**base)

    mesh = _ac_mesh()
    tr_j = SpreezeTrainer(cfg(mesh=mesh))
    rops.reset_trace_counts()
    tr_p = SpreezeTrainer(cfg(mesh=mesh, use_pallas=True))
    for tr in (tr_j, tr_p):
        tr._warmup()
        (tr.state, tr.replay, tr.env_states, tr.key,
         tr.last_metrics) = tr._megastep(tr.state, tr.replay,
                                         tr.env_states, tr.key)
    assert rops.TRACE_COUNTS["shard:ring_write"] > 0, rops.TRACE_COUNTS
    assert rops.TRACE_COUNTS["shard:ring_gather"] > 0, rops.TRACE_COUNTS
    assert int(tr_j.replay.ptr) == int(tr_p.replay.ptr)
    for k in tr_j.replay.data:
        np.testing.assert_allclose(np.asarray(tr_j.replay.data[k]),
                                   np.asarray(tr_p.replay.data[k]),
                                   rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(tr_j.last_metrics["critic_loss"]),
        np.asarray(tr_p.last_metrics["critic_loss"]),
        rtol=1e-3, atol=1e-5)


def test_mesh_pallas_rejects_indivisible_batch():
    """The Pallas opt-in forbids configs whose gather would silently
    fall back to jnp (batch_size not divisible by the ring shards)."""
    from repro.core import SpreezeConfig, SpreezeTrainer
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices for a batch axis of size 2")
    mesh = make_ac_mesh(1, 2)
    with pytest.raises(ValueError, match="ring shards"):
        SpreezeTrainer(SpreezeConfig(
            env_name="pendulum", algo="sac", num_envs=2, batch_size=33,
            chunk_len=4, warmup_frames=32, replay_capacity=256,
            mesh=mesh, use_pallas=True))
