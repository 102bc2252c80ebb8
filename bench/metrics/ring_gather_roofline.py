"""The ``ring_gather`` kernel's share of its roofline: the sampled rows'
payload bytes (batch x ``counts.payload_bytes``, each row from its ring
shard) at the HBM peak, over the kernel's device time."""
import counts

KERNEL = "ring_gather"


def read(run):
    return counts.kernel_roofline(run, KERNEL, run["traffic"]["batch_size"])
