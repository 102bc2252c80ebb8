"""Sharding-rule unit tests + small-mesh (subset of 1 device) lowering.

The 512-device production lowering is exercised by launch/dryrun.py (it
must own the XLA_FLAGS device-count override); these tests cover the
rule logic itself, which is pure metadata.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (MeshRules, param_spec,
                                        params_sharding_tree, standard_rules,
                                        spreeze_rules, use_rules)


from jax.sharding import AbstractMesh


def FakeMesh(shape: dict):
    """Abstract (device-less) mesh for rule-resolution tests."""
    return AbstractMesh(tuple(shape.values()), tuple(shape.keys()))


def _rules(pod=False):
    shape = ({"pod": 2, "data": 16, "model": 16} if pod
             else {"data": 16, "model": 16})
    return standard_rules(FakeMesh(shape))


def test_standard_rules_single_pod():
    r = _rules()
    assert r.batch == ("data",)
    assert r.seq == "model"
    assert r.spec("batch", "seq", None) == P(("data",), "model", None)


def test_standard_rules_multi_pod_folds_pod_into_batch():
    r = _rules(pod=True)
    assert r.batch == ("pod", "data")
    assert r.ac == "pod"
    assert r.axis_size(r.batch) == 32


def test_spreeze_rules_reserves_pod_for_ac():
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    r = spreeze_rules(mesh)
    assert r.batch == ("data",)          # batch no longer uses pod
    assert r.ac == "pod"                 # the actor/critic axis


def test_param_spec_2d_greedy():
    r = _rules()
    # (4096, 4096): largest dims get fsdp then tp
    assert param_spec((4096, 4096), rules=r) == P("data", "model")
    # stacked layer dim protected
    assert param_spec((32, 4096, 4096), stacked=True, rules=r) \
        == P(None, "data", "model")
    # indivisible dims stay unsharded
    assert param_spec((15, 7), rules=r) == P(None, None)
    # scalar
    assert param_spec((), rules=r) == P()


def test_param_spec_expert_dim():
    r = _rules()
    # kimi: 384 experts % 16 == 0 -> expert dim takes the model axis
    assert param_spec((384, 7168, 2048), expert_dim=0, rules=r) \
        == P("model", "data", None)
    # mixtral: 8 experts, not divisible -> falls back to intra-expert tp
    spec = param_spec((8, 4096, 14336), expert_dim=None, rules=r)
    assert spec[0] is None


def test_params_sharding_tree_paths():
    r = _rules()
    params = {
        "embed": jnp.zeros((512, 64)),
        "layers": {"w": jnp.zeros((4, 64, 64)),
                   "moe_w_gate": jnp.zeros((4, 16, 64, 128))},
    }
    tree = params_sharding_tree(params, r)
    # embed: plain 2D, both dims divisible -> fully 2D-sharded
    assert tree["embed"].spec == P("data", "model")
    # stacked layer param: dim0 protected
    assert tree["layers"]["w"].spec[0] is None
    # expert param: expert dim (1, stacked) gets model axis (16 % 16 == 0)
    assert tree["layers"]["moe_w_gate"].spec[1] == "model"


def test_shard_is_identity_without_rules():
    from repro.distributed.sharding import shard
    x = jnp.ones((4, 8))
    assert shard(x, "batch", None) is x


def test_divisibility_guards_in_launch_specs():
    from repro.configs import get_config, get_shape
    from repro.launch.specs import input_specs, shape_supported

    cfg = get_config("whisper-medium")
    specs = input_specs(cfg, get_shape("train_4k"))
    assert specs["frames"].shape == (256, 1500, 1024)
    ok, why = shape_supported(cfg, get_shape("long_500k"))
    assert not ok and "448" in why

    cfg = get_config("mamba2-130m")
    ok, _ = shape_supported(cfg, get_shape("long_500k"))
    assert ok


def test_model_flops_estimates():
    from repro.configs import get_config, get_shape
    from repro.launch.analysis import model_flops_estimate

    cfg = get_config("smollm-360m")
    f = model_flops_estimate(cfg, get_shape("train_4k"))
    # 6 * ~0.36e9 * 1.05e6 tokens ~ 2.3e15
    assert 1e15 < f < 4e15
    kimi = get_config("kimi-k2-1t-a32b")
    f2 = model_flops_estimate(kimi, get_shape("train_4k"))
    # active ~32B: 6 * 32e9 * 1.05e6 ~ 2e17
    assert 1e17 < f2 < 4e17


def test_collective_bytes_parser():
    from repro.launch.analysis import collective_bytes
    hlo = """
  %ag = bf16[16,256,960]{2,1,0} all-gather(%x), replica_groups={}
  %ar.1 = f32[128]{0} all-reduce(%y), to_apply=%sum
  %rs = (f32[64]{0}, f32[64]{0}) reduce-scatter(%a, %b)
  %cp = u32[2]{0} collective-permute(%c)
  %notacoll = f32[8]{0} add(%d, %e)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 16 * 256 * 960 * 2
    assert out["all-reduce"] == 128 * 4
    assert out["reduce-scatter"] == 2 * 64 * 4
    assert out["collective-permute"] == 8
    assert out["count"] == 4
