"""Operations and bytes a training cell needs, from its configuration's
widths and its mix's sizes, and the table of device peaks.

The counts of a configuration's networks are its networks module's
(``reference/networks_<name>.py``; ``networks_mlp.py`` counts the MLP
towers): FLOPs per env frame and ring rows gathered per sample. A module
without them gives no reading: the call raises, and never falls back to
another module's count.
"""
import json
import os

from reference import pipeline

FLOAT32_BYTES = 4


def flops_per_env_frame(cfg, mix):
    """Training FLOPs per env frame sampled, as the networks count them."""
    return pipeline.networks(cfg).flops_per_env_frame(cfg, mix)


def rows_per_sample(cfg, mix):
    """Ring rows the training example of one sample gathers."""
    return pipeline.networks(cfg).rows_per_sample(cfg, mix)


def payload_bytes(cfg):
    """A transition's own float32 fields: obs, act, rew, next_obs, done."""
    return FLOAT32_BYTES * (2 * cfg["obs_dim"] + cfg["act_dim"] + 2)


def peaks(device_kind):
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def kernel_roofline(run, kernel, rows_per_call):
    """% of the HBM roofline a ring kernel reaches, averaged over the
    cell's chips: each call moves ``rows_per_call`` payloads; None where
    the kernel did not run."""
    cfg = run["config"]
    hbm = peaks(run["device_kind"])["hbm_bytes_per_s"]
    shares = []
    for d in run["trace"]["devices"]:
        calls, secs = d["op_counts"].get(kernel, 0), d["ops_s"].get(kernel)
        if calls and secs:
            moved = calls * rows_per_call * payload_bytes(cfg)
            shares.append(100.0 * moved / hbm / secs)
    return sum(shares) / len(shares) if shares else None


def ms_per_update(run, ops):
    """Device ms per update step of the named ops, averaged over the
    cell's chips; None where none of them ran."""
    secs = [sum(d["ops_s"].get(op, 0.0) for op in ops)
            for d in run["trace"]["devices"]]
    if not any(secs) or not run["updates"]:
        return None
    return 1e3 * sum(secs) / len(secs) / run["updates"]
