"""Device time of the eval workers' ``eval_batch`` program over the
traced window, averaged over the cell's chips."""

MODULE = "jit_eval_batch"


def read(run):
    t = run["trace"]
    secs = [d["modules_s"].get(MODULE, 0.0) for d in t["devices"]]
    if not any(secs):
        return None
    return 100.0 * sum(secs) / len(secs) / t["window_s"]
