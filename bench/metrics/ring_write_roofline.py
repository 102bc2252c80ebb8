"""The ``ring_write`` kernel's share of its roofline: the transitions'
payload bytes it must write (rows x ``counts.payload_bytes``, each row
on its ring shard) at the HBM peak, over the kernel's device time."""
import counts

KERNEL = "ring_write"


def read(run):
    mix = run["traffic"]
    rows = mix["num_envs"] * mix["chunk_len"]
    return counts.kernel_roofline(run, KERNEL, rows)
