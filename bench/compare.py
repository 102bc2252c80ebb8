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

Both sides hand over per-leaf float64 norms, not trees (``norms``,
``change_norms``): each leaf is reduced as it comes off the device, so
the host holds the trained leaves before the followed dispatches and
about one leaf more, whatever the size of the model.
"""
import numpy as np

NAMES = ("first_loss_gap", "loss_gap", "grad_gap", "second_moment_gap",
         "change_gap", "undrawn_gap", "td_priority_gap")
NEGLIGIBLE = 1e-3
#: a priority value held by at least this many rows is a write-time
#: value (max priority when the rows were written); |TD| values are
#: distinct floats
WRITE_VALUE_ROWS = 8


def paths(tree, prefix=""):
    """Flatten nested dicts and lists -> [(path, leaf)], dict keys in
    sorted order, list items by index, joined by dots."""
    if isinstance(tree, dict):
        return [x for k in sorted(tree)
                for x in paths(tree[k], f"{prefix}{k}.")]
    if isinstance(tree, (list, tuple)):
        return [x for i, v in enumerate(tree)
                for x in paths(v, f"{prefix}{i}.")]
    return [(prefix[:-1], tree)]


def norm(x):
    """The float64 norm of a leaf read back to the host."""
    return float(np.linalg.norm(np.asarray(x, np.float64).ravel()))


def norms(tree, fetch=np.asarray):
    """{path: norm}, each leaf read back by ``fetch`` and reduced before
    the next is read."""
    return {k: norm(fetch(x)) for k, x in paths(tree)}


def host_leaves(tree, fetch=np.asarray):
    """{path: leaf} read back by ``fetch``, in the type it reads: the
    trained leaves before the followed dispatches, the one tree held on
    the host whole (the program's state is donated to each dispatch)."""
    return {k: fetch(x) for k, x in paths(tree)}


def change_norms(after, before, fetch=np.asarray):
    """-> ({path: norm of the leaf}, {path: norm of its change since
    ``before``, a ``host_leaves`` record}), one leaf at a time; the
    leaves of ``before`` are dropped as they are used."""
    before = dict(before)
    params, change = {}, {}
    for k, x in paths(after):
        a = np.asarray(fetch(x), np.float64)
        params[k] = norm(a)
        change[k] = norm(a - np.asarray(before.pop(k), np.float64))
    if before:
        raise ValueError(f"leaves differ: {sorted(before)}")
    return params, change


def leaf_gaps(got, want, keep):
    """{leaf: gap of norms over max(reference leaf norm, median)} of two
    ``norms`` records"""
    if set(got) != set(want):
        raise ValueError(f"leaves differ: {sorted(set(got) ^ set(want))}")
    med = float(np.median([want[k] for k in keep]))
    return {k: abs(got[k] - want[k]) / max(want[k], med, 1e-30)
            for k in keep}


def kept(ref):
    """Leaves whose reference gradient is not nought to rounding."""
    m = ref["first_moment"]
    med = float(np.median(list(m.values())))
    return sorted(k for k, v in m.items() if v >= NEGLIGIBLE * med)


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
    """``prog`` and ``ref``: {"losses" (dispatches, rounds), and the
    per-leaf ``norms`` "first_moment", "second_moment", "params" and
    "change", by the reference's leaf paths}, and "priorities" after the
    first dispatch under prioritized replay -> {name: number}."""
    keep = kept(ref)
    losses = loss_gaps(prog, ref)
    out = {
        "first_loss_gap": float(losses[0, 0]),
        "loss_gap": float(np.max(losses)),
        "grad_gap": max(leaf_gaps(prog["first_moment"],
                                  ref["first_moment"], keep).values()),
        "second_moment_gap": max(leaf_gaps(
            prog["second_moment"], ref["second_moment"], keep).values()),
        "change_gap": max(leaf_gaps(prog["change"], ref["change"],
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
    loss gap, each leaf's gap of the first moment, the second moment,
    the change and the parameters after the last dispatch, and the
    priority statistics of both sides."""
    keep = kept(ref)
    out = {"round_loss_gaps": loss_gaps(prog, ref).tolist(),
           "first_moment": leaf_gaps(prog["first_moment"],
                                     ref["first_moment"], keep),
           "second_moment": leaf_gaps(prog["second_moment"],
                                      ref["second_moment"], keep),
           "change": leaf_gaps(prog["change"], ref["change"], keep),
           "params": leaf_gaps(prog["params"], ref["params"], keep),
           "left_out": sorted(set(ref["first_moment"]) - set(keep))}
    if ref.get("priorities") is not None:
        out["priority_stats"] = {"program": priority_stats(
            prog["priorities"]), "reference": priority_stats(
                ref["priorities"])}
    return out
