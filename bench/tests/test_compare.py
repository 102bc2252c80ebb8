"""The comparison reads per-leaf norms that each side reduces one leaf
at a time; its readings are those of the whole-tree arithmetic it
replaced, to the last bit."""
import numpy as np
import pytest

import compare

RNG = np.random.default_rng(2**31 + 11)


def whole_tree_leaves(tree, prefix=""):
    """The whole-tree flattening: every leaf in float64 at once."""
    if isinstance(tree, dict):
        out = {}
        for k in sorted(tree):
            out.update(whole_tree_leaves(tree[k], f"{prefix}{k}."))
        return out
    if isinstance(tree, (list, tuple)):
        out = {}
        for i, v in enumerate(tree):
            out.update(whole_tree_leaves(v, f"{prefix}{i}."))
        return out
    return {prefix[:-1]: np.asarray(tree, np.float64)}


def whole_tree_norms(tree):
    return {k: float(np.linalg.norm(v.ravel()))
            for k, v in whole_tree_leaves(tree).items()}


def whole_tree_record(run):
    """A run of trees -> the norms record, by the whole-tree arithmetic:
    the change taken between the float64 trees."""
    after, before = (whole_tree_leaves(run["params"]),
                     whole_tree_leaves(run["params0"]))
    return {"losses": run["losses"], "priorities": run["priorities"],
            "first_moment": whole_tree_norms(run["first_moment"]),
            "second_moment": whole_tree_norms(run["second_moment"]),
            "params": whole_tree_norms(run["params"]),
            "change": whole_tree_norms(
                {k: after[k] - before[k] for k in after})}


def streamed_record(run):
    """The same run as the harness and the reference hand it over now."""
    fetch = lambda x: np.asarray(x, np.float32)
    params0 = compare.host_leaves(run["params0"], fetch)
    params, change = compare.change_norms(run["params"], params0, fetch)
    return {"losses": run["losses"], "priorities": run["priorities"],
            "first_moment": compare.norms(run["first_moment"], fetch),
            "second_moment": compare.norms(run["second_moment"], fetch),
            "params": params, "change": change}


def tree(scale):
    """20 float32 leaves: two towers of 3 layers; an encoder of 2 layers,
    an embedding, a scale and a leaf all but zero; a scalar."""
    leaf = lambda *shape: (scale * RNG.standard_normal(shape)
                           ).astype(np.float32)
    tower = lambda n, d: [{"w": leaf(d, d), "b": leaf(d)} for _ in range(n)]
    return {"actor": tower(3, 8), "q": tower(3, 6),
            "encoder": {"layers": tower(2, 5), "embed": leaf(7, 5),
                        "scale": leaf(5), "tiny": leaf(3) * 1e-9},
            "log_alpha": leaf()}


def run(scale):
    p0 = tree(scale)
    step = tree(1e-3 * scale)
    return {"losses": RNG.random((3, 4)).tolist(),
            "priorities": np.where(RNG.random(512) < 0.5, 1.0,
                                   RNG.random(512) + 1e-3
                                   ).astype(np.float32),
            "params0": p0,
            "params": {k: _add(p0[k], step[k]) for k in p0},
            "first_moment": tree(1e-4 * scale),
            "second_moment": tree(1e-6 * scale)}


def _add(a, b):
    if isinstance(a, dict):
        return {k: _add(a[k], b[k]) for k in a}
    if isinstance(a, list):
        return [_add(x, y) for x, y in zip(a, b)]
    return (a + b).astype(np.float32)


def test_the_synthetic_state_has_twenty_leaves():
    assert len(compare.paths(tree(1.0))) == 20


@pytest.mark.parametrize("scale", [1.0, 3e-2])
def test_streamed_norms_equal_the_whole_tree_readings(scale):
    prog, ref = run(scale), run(1.0)
    old = whole_tree_record(prog), whole_tree_record(ref)
    new = streamed_record(prog), streamed_record(ref)
    for a, b in zip(old, new):
        for key in ("first_moment", "second_moment", "params", "change"):
            assert a[key] == b[key], key
    assert compare.readings(*new) == compare.readings(*old)
    assert repr(compare.detail(*new)) == repr(compare.detail(*old))
    assert compare.readings(*new)["change_gap"] > 0


def test_change_norms_refuse_leaves_that_differ():
    before = compare.host_leaves({"a": np.ones(2), "b": np.ones(2)})
    with pytest.raises(ValueError, match="b"):
        compare.change_norms({"a": np.ones(2)}, before)
