"""FLOP and payload-byte counts against hand counts; the peak table."""
import pytest

import counts
from catalog import Catalog
from reference import networks_mlp

CAT = Catalog()


def config(name):
    return CAT._json("configs", f"{name}.json")


def test_pendulum_hand_counts():
    cfg = config("sac_pendulum")
    # actor 3-256-256-2, Q 4-256-256-1
    assert networks_mlp.tower_macs(cfg) == (3 * 256 + 256 * 256 + 256 * 2,
                                      4 * 256 + 256 * 256 + 256 * 1)
    assert networks_mlp.tower_macs(cfg) == (66816, 66816)
    assert networks_mlp.update_flops_per_sample(cfg) == 2 * 16 * 66816
    assert counts.payload_bytes(cfg) == 4 * (3 + 3 + 1 + 1 + 1) == 36


def test_hopper_hand_counts():
    cfg = config("sac_hopper")
    # actor 8-256-256-4, Q 10-256-256-1
    assert networks_mlp.tower_macs(cfg) == (68608, 68352)
    assert networks_mlp.update_flops_per_sample(cfg) == 2 * (4 * 68608
                                                       + 12 * 68352)
    assert counts.payload_bytes(cfg) == 4 * (8 + 8 + 2 + 1 + 1) == 80


def test_flops_per_env_frame_at_utd24():
    cfg, mix = config("sac_pendulum"), CAT._json("traffic",
                                                 "uniform_utd24.json")
    utd = 96 * 8192 / (1024 * 32)
    assert utd == 24
    assert counts.flops_per_env_frame(cfg, mix) == \
        2 * 66816 + 24 * 2 * 16 * 66816


def test_peak_table():
    v5e = counts.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v4")


def test_counts_are_the_networks_modules(monkeypatch):
    """The MLP configurations read the MLP module's counts; a networks
    module without counts gives no reading and raises, never the MLP's
    count."""
    import sys
    import types
    cfg, mix = config("sac_hopper"), CAT._json("traffic",
                                               "per_n3_utd2.json")
    assert counts.flops_per_env_frame(cfg, mix) == \
        networks_mlp.flops_per_env_frame(cfg, mix)
    assert counts.rows_per_sample(cfg, mix) == 1
    bare = types.ModuleType("reference.networks_bare")
    monkeypatch.setitem(sys.modules, "reference.networks_bare", bare)
    cfg = dict(cfg, networks="bare")
    with pytest.raises(AttributeError, match="flops_per_env_frame"):
        counts.flops_per_env_frame(cfg, mix)
    with pytest.raises(AttributeError, match="rows_per_sample"):
        counts.rows_per_sample(cfg, mix)
