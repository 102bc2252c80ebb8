"""Device time of PER's index selection per update step: the score
kernel (named ``per_topk`` in a v5e trace, after the function that
calls it) and XLA's top-k over the priorities (a ``sort``), averaged
over the cell's chips."""
import counts

OPS = ("per_scores", "per_topk", "sort")


def read(run):
    return counts.ms_per_update(run, OPS)
