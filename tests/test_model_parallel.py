"""model_parallel unit tests: trainer-mesh rules, replay sharding specs,
and the arch critic loss TD-target semantics (stop-gradient, target
params, hp.gamma)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.model_parallel import (make_arch_spreeze_losses,
                                       replay_sharding)
from repro.distributed.sharding import trainer_rules
from repro.launch.mesh import make_ac_mesh
from repro.rl.base import AlgoHP


def _ac_mesh():
    return make_ac_mesh(1, 1)


def test_trainer_rules_ac_placement():
    r = trainer_rules(_ac_mesh(), "ac")
    assert r.ac == "ac"
    assert r.batch == ("batch",)


def test_trainer_rules_dp_placement():
    r = trainer_rules(_ac_mesh(), "dp")
    assert r.ac is None
    assert r.batch == ("ac", "batch")
    with pytest.raises(ValueError):
        trainer_rules(_ac_mesh(), "bogus")


def test_replay_sharding_specs():
    from repro.replay import buffer as rb
    from repro.replay import prioritized as per
    rules = trainer_rules(_ac_mesh(), "ac")
    specs = rb.specs_for_env(3, 1)
    rep = rb.init_replay(64, specs)
    sh = replay_sharding(rep, rules)
    assert sh.rows.spec == P(("batch",), None)
    assert sh.layout == rep.layout
    assert sh.ptr.spec == P()
    psh = replay_sharding(per.init_prioritized(64, specs), rules)
    assert psh.base.rows.spec == P(("batch",), None)
    assert psh.priorities.spec == P(("batch",))
    assert psh.max_priority.spec == P()


# --------------------------------------------------------------------- #
# arch critic loss: TD target must not carry gradient (ISSUE 2 bugfix)
# --------------------------------------------------------------------- #

def _arch_setup(gamma: float):
    from repro.configs import get_config
    from repro.rl import networks as nets
    cfg = get_config("qwen2-0.5b").reduced()
    act_dim = 2
    key = jax.random.PRNGKey(0)
    ka, kq, kt = jax.random.split(key, 3)
    actor = nets.init_arch_policy(ka, cfg, act_dim, dtype=jnp.float32)
    q1 = nets.init_arch_q(kq, cfg, act_dim, dtype=jnp.float32)
    qs = jax.tree.map(lambda l: jnp.stack([l, l * 1.01]), q1)
    tgt = jax.tree.map(lambda l: l * 0.99, qs)
    B, S = 2, 8
    tokens = jax.random.randint(kt, (B, S), 0, cfg.vocab_size)
    act = jnp.tanh(jax.random.normal(kt, (B, act_dim)))
    rew = jnp.arange(B, dtype=jnp.float32)
    done = jnp.array([0.0, 1.0])
    _, critic_loss = make_arch_spreeze_losses(
        cfg, act_dim, dtype=jnp.float32, hp=AlgoHP(gamma=gamma))
    args = (qs, tgt, actor, tokens, act, rew, done,
            jax.random.PRNGKey(1))
    return critic_loss, args


def test_arch_critic_target_carries_no_gradient():
    critic_loss, args = _arch_setup(gamma=0.99)
    tgt_grads = jax.grad(critic_loss, argnums=1)(*args)
    for leaf in jax.tree.leaves(tgt_grads):
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.zeros_like(np.asarray(leaf)))
    # while the online critic does receive gradient
    q_grads = jax.grad(critic_loss, argnums=0)(*args)
    assert any(float(jnp.abs(l).max()) > 0
               for l in jax.tree.leaves(q_grads))


def test_arch_critic_uses_hp_gamma():
    l_hi, args = _arch_setup(gamma=0.99)
    l_lo, _ = _arch_setup(gamma=0.0)
    # gamma=0 target is just rew: the two losses must differ on the
    # not-done row (identical inputs otherwise)
    assert float(l_hi(*args)) != pytest.approx(float(l_lo(*args)))
