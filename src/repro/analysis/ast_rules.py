"""Layer 3: AST rules — jit-boundary hazards the jaxpr can't see.

The jaxpr layer proves properties of what IS traced; this layer lints the
Python that decides WHAT gets traced and WHEN the host blocks on the
device.  Registry-driven to stay precise: a small set of known traced
functions, known hot host driver paths, and known jitted callables — so
``np.asarray`` on genuinely-host data (fold bookkeeping, grid cursors)
never false-positives.

Rules (one finding per (rule, file::qualname); the detail aggregates
line numbers so unrelated edits don't churn the baseline):

  * ``ast/host-sync-in-traced``   ``float()``/``int()``/``.item()``/
    ``np.asarray``/``np.array``/``jax.device_get`` inside a traced
    function — a concretization error waiting to happen (or an
    already-silent host round-trip when the fn also runs eagerly).
  * ``ast/tracer-branch``         Python ``if`` on a non-static parameter
    of a traced function (``is None``/``is not None`` pytree-structure
    tests are exempt; static params — max_iter, screen, ... — are
    trace-time constants).
  * ``ast/jit-dispatch-in-loop``  a known jitted callable invoked inside a
    ``for``/``while`` of a hot host path: each iteration pays dispatch
    (and usually a sync).  The engine drivers' one-dispatch-per-segment
    loops are baselined by design; NEW entries mean a batching regression.
  * ``ast/host-sync-in-hot-loop`` taint analysis: values returned by
    jitted callables (or unpacked from ``launch.outputs``) are
    device-resident; ``float``/``int``/``np.asarray``/``.item`` applied
    to them inside a loop forces a blocking transfer per iteration.
  * ``ast/block-until-ready``     ``jax.block_until_ready`` outside the
    sanctioned sites (the fold drivers' setup barriers in ``cv.py``) —
    every other site must justify itself in the baseline.
  * ``ast/deprecated-shim``       (warning) calls to the legacy entry
    points (``sgl_cv``/``nn_lasso_cv``/``stability_selection``) from
    non-shim engine code.
"""
from __future__ import annotations

import ast
import os

from .findings import Finding

# ---------------------------------------------------------------------------
# Registries — the precision of every rule comes from here.
# ---------------------------------------------------------------------------

# functions whose bodies are traced by jit/vmap/scan (top-level name or
# method name; nested defs inherit the enclosing registration)
TRACED_FUNCTIONS = {
    "core/solver.py": {"fista_sgl", "fista_nn_lasso", "solve_sgl",
                       "solve_nn_lasso"},
    "core/path_engine.py": {"sweep_sgl_core", "sweep_nn_core", "_xtv",
                            "_padded_prox"},
    "core/cv.py": {"_screen_folds_sgl", "_screen_folds_nn"},
    "core/screening.py": {"tlfre_screen_grid", "tlfre_screen_grid_folds",
                          "gap_safe_screen_grid",
                          "gap_safe_screen_grid_folds",
                          "gap_safe_grid_radii", "grid_ball_geometry"},
    "core/dpc.py": {"dpc_screen_grid", "dpc_screen_grid_folds",
                    "gap_safe_screen_grid_nn", "dual_scaling_nn",
                    "lambda_max_nn", "normal_vector_nn"},
    "core/lambda_max.py": {"group_shrink_roots", "lambda_max_sgl",
                           "dual_scaling_sgl", "_padded_segment_roots",
                           "lambda1_max", "lambda2_max"},
    "core/fenchel.py": {"shrink", "proj_binf", "dual_decompose",
                        "sgl_feasibility_margin", "sgl_dual_feasible",
                        "sgl_dual_objective", "sgl_primal_objective",
                        "group_inf_norms"},
    "core/estimation.py": {"normal_vector_sgl"},
    "core/linalg.py": {"spectral_norm", "column_norms"},
    "core/session.py": {"_fold_duals_sgl", "_fold_duals_nn"},
    "launch/sgl_serve.py": {"_batch_lambda_max", "_batch_refit"},
    "kernels/ops.py": {"xtv", "screen_norms", "screen_norms_batched",
                       "screen_norms_folds", "dpc_screen_folds",
                       "sgl_prox_padded"},
}

# host driver paths where per-iteration dispatch/sync is the hazard
HOT_HOST_PATHS = {
    "core/path_engine.py": {"sgl_path_batched", "nn_lasso_path_batched"},
    "core/cv.py": {"screen", "harvest", "make_launch", "run",
                   "sgl_fold_paths", "nn_fold_paths"},
    "launch/sgl_serve.py": {"_run_batch", "drain"},
    "core/session.py": {"path", "cv", "refine", "stability",
                        "_fold_state_at"},
}

# callables whose results are device-resident (jit-compiled dispatches)
JITTED_CALLABLES = {
    "solve_sgl", "solve_nn_lasso", "fista_sgl", "fista_nn_lasso",
    "lambda_max_sgl", "lambda_max_nn", "spectral_norm", "_sweep_sgl",
    "_sweep_nn", "_tlfre_grid_jit", "_gap_safe_grid_jit",
    "_gap_safe_radii_jit", "_dpc_grid_jit", "_gap_safe_nn_jit",
    "_screen_folds_sgl", "_screen_folds_nn", "_spectral_norms_f",
    "_fold_duals_sgl", "_fold_duals_nn", "_batch_lambda_max",
    "_batch_refit",
}

# attributes whose read yields device arrays (the launch-output handoff)
DEVICE_ATTRS = {"outputs"}

# parameters that are jit-static (branching on them is trace-time control
# flow, not a tracer leak)
STATIC_PARAM_NAMES = {
    "max_iter", "check_every", "use_pallas", "interpret", "screen",
    "penalty", "prox", "centered", "schedule", "kind", "mesh", "n_folds",
    "specnorm_method", "safety", "engine", "selection", "center",
    # Loss singletons are frozen hashable dataclasses closed over at trace
    # time — branching on loss.gamma etc. is trace-time control flow
    "loss",
}

# (file, enclosing function) pairs where block_until_ready is sanctioned:
# the fold drivers' setup barriers (timing boundary before the scheduler)
BLOCK_UNTIL_READY_ALLOWLIST = {
    ("core/cv.py", "sgl_fold_paths"),
    ("core/cv.py", "nn_fold_paths"),
}

DEPRECATED_SHIMS = {"sgl_cv", "nn_lasso_cv", "stability_selection"}
# the shims' own home + the compat facade re-exporting them
SHIM_FILES = {"core/cv.py", "core/path.py", "api.py"}

_SYNC_NP = {"asarray", "array", "ascontiguousarray"}


def _call_name(node: ast.Call):
    """Trailing identifier of the called expression (Name or Attribute)."""
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def _call_root(node: ast.Call):
    f = node.func
    while isinstance(f, ast.Attribute):
        f = f.value
    return f.id if isinstance(f, ast.Name) else None


def _is_sync_call(node: ast.Call) -> bool:
    name = _call_name(node)
    if name in ("float", "int") and isinstance(node.func, ast.Name) \
            and node.args:
        return True
    if name == "item" and isinstance(node.func, ast.Attribute):
        return True
    if name in _SYNC_NP and _call_root(node) in ("np", "numpy"):
        return True
    if name in ("device_get", "_pull"):     # _pull: the engine's counted
        return True                         # np.asarray (core/path_engine)
    return False


def _names_in(node) -> set:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _assigned_names(target) -> list:
    """Flat Name ids bound by an assignment target (tuples unpacked)."""
    if isinstance(target, ast.Name):
        return [target.id]
    if isinstance(target, (ast.Tuple, ast.List)):
        out = []
        for elt in target.elts:
            out.extend(_assigned_names(elt))
        return out
    return []


class _TopFns(ast.NodeVisitor):
    """Collect top-level functions and class methods with qualnames."""

    def __init__(self):
        self.fns = []           # (qualname, bare name, node)
        self._cls = None

    def visit_ClassDef(self, node):
        prev, self._cls = self._cls, node.name
        for child in node.body:
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.fns.append((f"{node.name}.{child.name}", child.name,
                                 child))
        self._cls = prev

    def visit_FunctionDef(self, node):
        self.fns.append((node.name, node.name, node))

    visit_AsyncFunctionDef = visit_FunctionDef


def _walk_with_loops(body, in_loop=False):
    """Yield (node, in_loop) over statements/expressions, tracking
    For/While nesting (comprehensions deliberately NOT counted: their
    iterables are materialised host data by the time they run)."""
    for node in body:
        yield node, in_loop
        child_loop = in_loop or isinstance(node, (ast.For, ast.While))
        yield from _walk_with_loops(list(ast.iter_child_nodes(node)),
                                    child_loop)


def _agg(findings_map, rule, severity, loc, line, what):
    entry = findings_map.setdefault((rule, loc), [severity, []])
    entry[1].append((line, what))


def _emit(findings_map):
    out = []
    for (rule, loc), (severity, hits) in sorted(findings_map.items()):
        lines = sorted({ln for ln, _ in hits})
        whats = sorted({w for _, w in hits})
        out.append(Finding(
            rule, severity, loc,
            f"{', '.join(whats)} at line(s) "
            f"{', '.join(map(str, lines))}"))
    return out


def _lint_traced(qual, node, relpath, fmap):
    params = {a.arg for a in (node.args.posonlyargs + node.args.args
                              + node.args.kwonlyargs)}
    dyn = params - STATIC_PARAM_NAMES - {"self"}
    loc = f"{relpath}::{qual}"
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) and _is_sync_call(sub):
            _agg(fmap, "ast/host-sync-in-traced", "error", loc, sub.lineno,
                 f"{_call_name(sub)}() on a traced value")
        elif isinstance(sub, ast.If):
            # names tested only as `x is None` / `x.attr is None` (either
            # polarity) probe the pytree STRUCTURE, not the tracer value:
            # an optional leaf (e.g. spec.feature_weights) is part of the
            # treedef, so the branch is resolved at trace time
            exempt = set()
            for cmp_ in ast.walk(sub.test):
                if (isinstance(cmp_, ast.Compare)
                        and len(cmp_.ops) == 1
                        and isinstance(cmp_.ops[0], (ast.Is, ast.IsNot))):
                    root = cmp_.left
                    while isinstance(root, ast.Attribute):
                        root = root.value
                    if isinstance(root, ast.Name):
                        exempt.add(root.id)
            offenders = (_names_in(sub.test) & dyn) - exempt
            if offenders:
                _agg(fmap, "ast/tracer-branch", "error", loc, sub.lineno,
                     f"Python if on traced parameter(s) "
                     f"{'/'.join(sorted(offenders))}")


def _lint_hot(qual, node, relpath, fmap):
    loc = f"{relpath}::{qual}"
    # taint pass: names bound from jitted calls / device attrs, plus one
    # propagation sweep through subscript/attribute/slice re-binding
    tainted: set = set()
    for _ in range(3):
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Assign):
                continue
            v = sub.value
            src_tainted = False
            if isinstance(v, ast.Call) and _call_name(v) in \
                    JITTED_CALLABLES:
                src_tainted = True
            elif isinstance(v, ast.Attribute) and v.attr in DEVICE_ATTRS:
                src_tainted = True
            elif _names_in(v) & tainted and not any(
                    isinstance(c, ast.Call) and _is_sync_call(c)
                    for c in ast.walk(v)):
                # slices/arithmetic of device values stay on device; a
                # value passing through np.asarray/float/... anywhere in
                # the expression lands on host (the sync itself is what
                # the in-loop rule flags)
                src_tainted = True
            if src_tainted:
                for t in sub.targets:
                    tainted.update(_assigned_names(t))
    for sub, in_loop in _walk_with_loops(node.body):
        if not isinstance(sub, ast.Call) or not in_loop:
            continue
        name = _call_name(sub)
        if name in JITTED_CALLABLES:
            _agg(fmap, "ast/jit-dispatch-in-loop", "error", loc,
                 sub.lineno, f"{name}() dispatched per loop iteration")
        if _is_sync_call(sub):
            arg_names, direct_jit = set(), False
            for a in list(sub.args) + [kw.value for kw in sub.keywords]:
                arg_names |= _names_in(a)
                direct_jit = direct_jit or any(
                    isinstance(c, ast.Call)
                    and _call_name(c) in JITTED_CALLABLES
                    for c in ast.walk(a))
            if (arg_names & tainted) or direct_jit:
                _agg(fmap, "ast/host-sync-in-hot-loop", "error", loc,
                     sub.lineno,
                     f"{name}() forces a device->host sync per "
                     f"loop iteration")


def lint_source(src: str, relpath: str, *, traced=None, hot=None,
                allow_block=None, shim_files=None) -> list:
    """Lint one file's source.  Registry overrides exist for the seeded
    fixture tests."""
    traced = TRACED_FUNCTIONS if traced is None else traced
    hot = HOT_HOST_PATHS if hot is None else hot
    allow_block = (BLOCK_UNTIL_READY_ALLOWLIST if allow_block is None
                   else allow_block)
    shim_files = SHIM_FILES if shim_files is None else shim_files
    tree = ast.parse(src)
    top = _TopFns()
    top.visit(tree)
    fmap: dict = {}

    traced_names = traced.get(relpath, set())
    hot_names = hot.get(relpath, set())
    for qual, bare, node in top.fns:
        if bare in traced_names:
            _lint_traced(qual, node, relpath, fmap)
        if bare in hot_names:
            _lint_hot(qual, node, relpath, fmap)

    # file-wide rules
    def enclosing(lineno):
        best = "<module>"
        for qual, _, node in top.fns:
            if node.lineno <= lineno <= (node.end_lineno or node.lineno):
                best = qual
        return best

    for sub in ast.walk(tree):
        if not isinstance(sub, ast.Call):
            continue
        name = _call_name(sub)
        if name == "block_until_ready":
            fn = enclosing(sub.lineno)
            bare = fn.split(".")[-1]
            if (relpath, bare) not in allow_block:
                _agg(fmap, "ast/block-until-ready", "error",
                     f"{relpath}::{fn}", sub.lineno,
                     "block_until_ready outside the sanctioned sites")
        elif name in DEPRECATED_SHIMS and relpath not in shim_files:
            fn = enclosing(sub.lineno)
            _agg(fmap, "ast/deprecated-shim", "warning",
                 f"{relpath}::{fn}", sub.lineno,
                 f"call to legacy shim {name}()")
    return _emit(fmap)


def run(root=None) -> list:
    """Lint every file under src/repro (excluding this analyzer)."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings = []
    for dirpath, _, files in os.walk(root):
        if os.path.basename(dirpath) == "analysis":
            continue
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            relpath = os.path.relpath(path, root).replace(os.sep, "/")
            with open(path) as fh:
                src = fh.read()
            findings.extend(lint_source(src, relpath))
    return findings
