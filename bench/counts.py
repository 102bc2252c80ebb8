"""Operations and bytes a training cell needs, from its configuration's
widths and its mix's sizes, and the table of device peaks.

FLOPs count the matrix multiplications of the MLP towers (2 per
multiply-add). One SAC update of one sampled row runs the actor 4 times
and a Q tower 12 times, in forward-pass equivalents:
- critic: actor forward on next_obs (1 actor); both target towers (2 Q);
  both online towers forward and backward (2 + 4 Q);
- actor: actor forward and backward (1 + 2 actor); both towers forward
  and backward to the action only (2 + 2 Q).
Each env frame adds one actor forward for the policy sample. Eval
episodes are not training work and are not counted.
"""
import json
import os

FLOAT32_BYTES = 4


def mlp_macs(dims):
    return sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def tower_macs(cfg):
    """-> (actor MACs, one Q tower's MACs) per row."""
    h = list(cfg["hidden"])
    obs, act = cfg["obs_dim"], cfg["act_dim"]
    return (mlp_macs([obs] + h + [2 * act]),
            mlp_macs([obs + act] + h + [1]))


def update_flops_per_sample(cfg):
    actor, q = tower_macs(cfg)
    return 2 * (4 * actor + 12 * q)


def flops_per_env_frame(cfg, mix):
    """Training FLOPs per env frame sampled: the policy forward plus the
    updates, UTD (rows updated per frame) of them."""
    utd = (mix["updates_per_round"] * mix["batch_size"]
           / (mix["num_envs"] * mix["chunk_len"]))
    return 2 * tower_macs(cfg)[0] + utd * update_flops_per_sample(cfg)


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
