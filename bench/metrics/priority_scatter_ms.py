"""Device time of the ``priority_scatter`` kernel per update step,
averaged over the cell's chips."""
import counts

OPS = ("priority_scatter",)


def read(run):
    return counts.ms_per_update(run, OPS)
