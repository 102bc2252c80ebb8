"""The comparison that decides ``correct`` for a training cell.

The program and the plain reference both run the warmup and the first
three dispatches from one seed (a dispatch, R rounds of K updates, is
the program's smallest step). The readings:

- ``first_loss_gap``: the relative gap of the first round's mean critic
  loss, the finest step whose loss the program reports;
- ``loss_gap``: the largest relative gap of a round's mean critic loss,
  over the rounds of the three dispatches;
- ``grad_gap``: Adam's first moment after the first dispatch (the
  gradients as the optimizer got them), the worst leaf's gap;
- ``second_moment_gap``: Adam's second moment after the first
  dispatch, the worst leaf's gap;
- ``change_gap``: the change of the trained leaves over the three
  dispatches, the worst leaf's gap;
- under prioritized replay, from the priority vector after the first
  dispatch: ``undrawn_gap``, the relative gap of the number of written
  rows that still hold the priority they were written with (never drawn
  and re-prioritised), and ``td_priority_gap``, the relative gap of the
  mean priority of the re-prioritised rows (|TD error| + eps).

A leaf's gap is the gap between the two norms (not the norm of the
difference), over the reference's norm of that leaf or of the median
leaf, whichever is larger. Leaves whose reference first moment is under
a thousandth of the median leaf's are left out: Adam moves them by
round-off alone. A cell compares the readings its ``limits/<cell>.json``
names, each against its limit; the others are printed.
"""
import numpy as np

NAMES = ("first_loss_gap", "loss_gap", "grad_gap", "second_moment_gap",
         "change_gap", "undrawn_gap", "td_priority_gap")
NEGLIGIBLE = 1e-3
#: a priority value held by at least this many rows is a write-time
#: value (max priority when the rows were written); |TD| values are
#: distinct floats
WRITE_VALUE_ROWS = 8


def leaves(tree, prefix=""):
    """Flatten nested dicts and lists -> {path: float64 array}."""
    if isinstance(tree, dict):
        out = {}
        for k in sorted(tree):
            out.update(leaves(tree[k], f"{prefix}{k}."))
        return out
    if isinstance(tree, (list, tuple)):
        out = {}
        for i, v in enumerate(tree):
            out.update(leaves(v, f"{prefix}{i}."))
        return out
    return {prefix[:-1]: np.asarray(tree, np.float64)}


def norms(tree):
    return {k: float(np.linalg.norm(v.ravel()))
            for k, v in leaves(tree).items()}


def leaf_gaps(got, want, keep):
    """{leaf: gap of norms over max(reference leaf norm, median)}"""
    got, want = norms(got), norms(want)
    if set(got) != set(want):
        raise ValueError(f"leaves differ: {sorted(set(got) ^ set(want))}")
    med = float(np.median([want[k] for k in keep]))
    return {k: abs(got[k] - want[k]) / max(want[k], med, 1e-30)
            for k in keep}


def kept(ref):
    """Leaves whose reference gradient is not nought to rounding."""
    m = norms(ref["first_moment"])
    med = float(np.median(list(m.values())))
    return sorted(k for k, v in m.items() if v >= NEGLIGIBLE * med)


def change(run):
    after, before = leaves(run["params"]), leaves(run["params0"])
    return {k: after[k] - before[k] for k in after}


def loss_gaps(prog, ref):
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    if lp.shape != lr.shape:
        raise ValueError(f"round losses of shape {lp.shape} against "
                         f"{lr.shape}")
    return np.abs(lp - lr) / np.abs(lr)


def priority_stats(pri):
    """-> (rows still at a write-time priority, mean priority of the
    re-prioritised rows) of a priority vector; unwritten rows are 0."""
    p = np.asarray(pri, np.float32)
    p = p[p > 0]
    values, counts = np.unique(p, return_counts=True)
    at_write = counts >= WRITE_VALUE_ROWS
    undrawn = int(counts[at_write].sum())
    drawn = values[~at_write]
    mean = float((drawn.astype(np.float64) * counts[~at_write]).sum()
                 / counts[~at_write].sum()) if drawn.size else float("nan")
    return undrawn, mean


def relative(got, want):
    if not np.isfinite(got) or not np.isfinite(want) or want == 0:
        return float("inf")
    return abs(got - want) / abs(want)


def readings(prog, ref):
    """``prog`` and ``ref``: {"losses" (dispatches, rounds),
    "first_moment", "second_moment", "params0", "params"} in the
    reference's tree layout, and "priorities" after the first dispatch
    under prioritized replay -> {name: number}."""
    keep = kept(ref)
    losses = loss_gaps(prog, ref)
    out = {
        "first_loss_gap": float(losses[0, 0]),
        "loss_gap": float(np.max(losses)),
        "grad_gap": max(leaf_gaps(prog["first_moment"],
                                  ref["first_moment"], keep).values()),
        "second_moment_gap": max(leaf_gaps(
            prog["second_moment"], ref["second_moment"], keep).values()),
        "change_gap": max(leaf_gaps(change(prog), change(ref),
                                    keep).values()),
    }
    if ref.get("priorities") is not None:
        (u_p, m_p), (u_r, m_r) = (priority_stats(prog["priorities"]),
                                  priority_stats(ref["priorities"]))
        out["undrawn_gap"] = relative(u_p, u_r)
        out["td_priority_gap"] = relative(m_p, m_r)
    return out


def checks(values, limits):
    """-> ({name: {"value", "limit"}}, all within their limits) for the
    numbers the cell's limits file names. A number that is missing or
    not finite fails."""
    out = {n: {"value": values.get(n, float("nan")), "limit": limits[n]}
           for n in NAMES if n in limits}
    ok = bool(out) and all(np.isfinite(v["value"])
                           and v["value"] <= v["limit"]
                           for v in out.values())
    return out, ok


def detail(prog, ref):
    """What the readings are made of, for setting limits: each round's
    loss gap, each leaf's gap of the first moment, the second moment and
    the change, and the priority statistics of both sides."""
    keep = kept(ref)
    out = {"round_loss_gaps": loss_gaps(prog, ref).tolist(),
           "first_moment": leaf_gaps(prog["first_moment"],
                                     ref["first_moment"], keep),
           "second_moment": leaf_gaps(prog["second_moment"],
                                      ref["second_moment"], keep),
           "change": leaf_gaps(change(prog), change(ref), keep),
           "left_out": sorted(set(norms(ref["first_moment"])) - set(keep))}
    if ref.get("priorities") is not None:
        out["priority_stats"] = {"program": priority_stats(
            prog["priorities"]), "reference": priority_stats(
                ref["priorities"])}
    return out
