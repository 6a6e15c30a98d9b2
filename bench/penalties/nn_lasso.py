"""Nonnegative Lasso: the program's ``Problem.nn_lasso``, held to the f64
nonnegative-Lasso duality gap of ``bench.reference``."""
from bench import reference


def structure(sizes):
    return None


def problem(X, y, structure):
    from repro.core import Problem
    return Problem.nn_lasso(X, y)


def gap_ratios(X, Y, lams, B, *, tol, sizes, plan):
    return reference.gap_ratios(X, Y, lams, B, tol)
