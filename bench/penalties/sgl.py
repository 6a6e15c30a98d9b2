"""Sparse-group lasso with the squared loss: the program's ``Problem.sgl``
on one ``GroupSpec`` built once per run, held to the f64 SGL duality gap
of ``bench.reference``."""
from bench import reference


def structure(sizes):
    from repro.core import GroupSpec
    return GroupSpec.from_sizes(sizes.tolist())


def problem(X, y, structure):
    from repro.core import Problem
    return Problem.sgl(X, y, groups=structure)


def gap_ratios(X, Y, lams, B, *, tol, sizes, plan):
    return reference.gap_ratios(X, Y, lams, B, tol, sizes=sizes,
                                alpha=float(plan.get("alpha", 1.0)))
