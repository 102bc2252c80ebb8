"""The training step's share of the chips' bf16 peak: the networks'
matmul FLOPs per env frame (``counts.flops_per_env_frame``, from the
configuration's networks module) times the traced window's env frames
per second, over chips times peak."""
import counts


def read(run):
    peak = counts.peaks(run["device_kind"])["bf16_flops_per_s"]
    rate = run["frames"] / run["trace"]["window_s"]
    flops = counts.flops_per_env_frame(run["config"], run["traffic"])
    return 100.0 * flops * rate / (run["chips"] * peak)
