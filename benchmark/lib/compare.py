"""The numbers a training cell's ``correct`` compares, computed alike for the
program and for the reference from trees ``{layer: {"w", "b"}}``.

Each is a gap between two norms (the program's and the reference's), never
the norm of a difference, taken by the worst leaf and measured against the
reference's norm of that leaf or of the median leaf, whichever is larger:
some leaves' gradients are all but zero."""

import statistics

import jax.numpy as jnp


def leaf_norms(tree):
    """{"<layer>.<leaf>": norm} as host floats."""
    return {"%s.%s" % (i, n): float(jnp.linalg.norm(a.ravel()))
            for i, leaves in tree.items() for n, a in leaves.items()}


def first_gradient_norms(params0, velocity1, sgd):
    """Norms of the first gradient as the optimizer got it (batch mean,
    before weight decay), worked out from the velocity after one step from
    rest: v1 = -lr (g + wd w0) for weights, -lr g for biases."""
    lr, wd = sgd["learning_rate"], sgd["weight_decay"]
    return leaf_norms({
        i: {"w": -velocity1[i]["w"] / lr - wd * params0[i]["w"],
            "b": -velocity1[i]["b"] / lr} for i in params0})


def change_norms(params0, params1):
    return leaf_norms({i: {n: params1[i][n] - params0[i][n]
                           for n in params0[i]} for i in params0})


def worst_gap(program, reference, leave_out=()):
    """(worst relative gap of norms over the leaves, that leaf's name)."""
    floor = statistics.median(reference.values())
    worst, where = 0.0, None
    for name, ref in reference.items():
        if name in leave_out:
            continue
        gap = abs(program[name] - ref) / max(ref, floor)
        if gap >= worst:
            worst, where = gap, name
    return worst, where


def median_gap(program, reference):
    """The median leaf's relative gap of norms: steady where one leaf steps
    (read beside ``worst_gap`` while the parked training cell's limits are
    worked out; not compared yet)."""
    floor = statistics.median(reference.values())
    return statistics.median(abs(program[n] - r) / max(r, floor)
                             for n, r in reference.items())


def all_but_zero(reference_gradient, share=1e-3):
    """Leaves whose reference gradient is under ``share`` of the median
    leaf's: they move by round-off alone and are left out of the change."""
    floor = share * statistics.median(reference_gradient.values())
    return {n for n, v in reference_gradient.items() if v < floor}
