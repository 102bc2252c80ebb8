"""The ``ring_gather`` kernel's share of its roofline: the sampled rows'
payload bytes (batch x the rows a sample gathers,
``counts.rows_per_sample``, x ``counts.payload_bytes``, each row from its
ring shard) at the HBM peak, over the kernel's device time."""
import counts

KERNEL = "ring_gather"


def read(run):
    mix = run["traffic"]
    rows = mix["batch_size"] * counts.rows_per_sample(run["config"], mix)
    return counts.kernel_roofline(run, KERNEL, rows)
