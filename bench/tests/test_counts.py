"""FLOP and payload-byte counts against hand counts; the peak table."""
import pytest

import counts
from catalog import Catalog

CAT = Catalog()


def config(name):
    return CAT._json("configs", f"{name}.json")


def test_pendulum_hand_counts():
    cfg = config("sac_pendulum")
    # actor 3-256-256-2, Q 4-256-256-1
    assert counts.tower_macs(cfg) == (3 * 256 + 256 * 256 + 256 * 2,
                                      4 * 256 + 256 * 256 + 256 * 1)
    assert counts.tower_macs(cfg) == (66816, 66816)
    assert counts.update_flops_per_sample(cfg) == 2 * 16 * 66816
    assert counts.payload_bytes(cfg) == 4 * (3 + 3 + 1 + 1 + 1) == 36


def test_hopper_hand_counts():
    cfg = config("sac_hopper")
    # actor 8-256-256-4, Q 10-256-256-1
    assert counts.tower_macs(cfg) == (68608, 68352)
    assert counts.update_flops_per_sample(cfg) == 2 * (4 * 68608
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
