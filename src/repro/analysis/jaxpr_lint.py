"""Layer 1: jaxpr lint — trace the engine's jitted entry points and walk
the ClosedJaxpr for invariant violations.

The engine's dtype-purity and host-interaction story is a property of the
*traced computation graph*, so it can be proven at trace time instead of
observed at runtime:

  * ``jaxpr/f64-downcast``   (f64 traces)  a ``convert_element_type`` that
    narrows a float — an exactness path silently rounding through f32.
  * ``jaxpr/pallas-on-f64``  (f64 traces)  a ``pallas_call`` primitive in
    the graph at all — the f32 kernels must be unreachable
    (``_pallas_active`` gate; static proof behind the runtime
    ``n_pallas_screens == 0`` counter).
  * ``jaxpr/upcast-in-loop`` (f32 traces)  a float widening inside a
    scan/while body — hot-loop compute silently promoted to f64 (the
    classic culprit: float64 ``GroupSpec.weights`` leaking into FISTA).
  * ``jaxpr/transfer-in-loop``  ``device_put`` / callback / infeed
    primitives inside a loop body — hidden host round-trips per iteration.
  * ``jaxpr/accum-downcast``  a ``dot_general`` whose output float width is
    below its widest float operand — low-precision accumulation.
  * ``jaxpr/full-gemm-count``  sweep entries must issue EXACTLY one
    full-X (p-column) GEMM inside the scan body per certification row
    (the Lemma-9 dual recovery); more means the bucketing broke.

Entry points are traced on a tiny representative problem whose dimensions
are all distinct (N=8, p=20, p_bucket=12, G=5, g_bucket=4, n_max=6, L=4,
K=2), so "touches the full p dim" is unambiguous in avals.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr

from .findings import Finding

_TRANSFER_PRIMS = frozenset({
    "device_put", "infeed", "outfeed", "host_callback_call",
    "outside_call", "pure_callback", "io_callback", "debug_callback",
})
_LOOP_PRIMS = frozenset({"scan", "while"})


# ---------------------------------------------------------------------------
# Generic jaxpr walking
# ---------------------------------------------------------------------------

def _flatten(v):
    if isinstance(v, (tuple, list)):
        for x in v:
            yield from _flatten(x)
    else:
        yield v


def _sub_jaxprs(eqn):
    """Every Jaxpr nested in an eqn's params (scan body, cond branches,
    pjit jaxpr, custom_*_call, pallas_call body, ...)."""
    for v in eqn.params.values():
        for x in _flatten(v):
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def iter_eqns(jaxpr, in_loop: bool = False):
    """Yield ``(eqn, in_loop)`` over the whole nested jaxpr tree.
    ``in_loop`` is True inside any scan/while body (cond/pjit inherit the
    enclosing context)."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        child_loop = in_loop or eqn.primitive.name in _LOOP_PRIMS
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, child_loop)


def _float_bits(dtype) -> int:
    dtype = np.dtype(dtype)
    return np.finfo(dtype).bits if np.issubdtype(dtype, np.floating) else 0


def lint_closed_jaxpr(name: str, closed, *, dtype: str,
                      full_p=None, expect_full_gemms=None) -> list:
    """Walk one entry point's ClosedJaxpr and report findings.

    ``dtype``: "float32" | "float64" — which purity contract applies.
    ``full_p``: the full feature count of the representative problem; with
    ``expect_full_gemms`` set, in-loop dot_generals whose operands carry the
    full-p dim are counted and compared against it.
    """
    findings = []
    gemms_in_loop = 0
    for eqn, in_loop in iter_eqns(closed.jaxpr):
        prim = eqn.primitive.name
        if prim == "convert_element_type":
            src = _float_bits(eqn.invars[0].aval.dtype)
            dst = _float_bits(eqn.params["new_dtype"])
            if src and dst:
                if dtype == "float64" and dst < src:
                    findings.append(Finding(
                        "jaxpr/f64-downcast", "error", f"{name}",
                        f"float{src} -> float{dst} convert in the f64 "
                        f"trace of {name} (in_loop={in_loop})"))
                if dtype == "float32" and dst > src and in_loop:
                    findings.append(Finding(
                        "jaxpr/upcast-in-loop", "error", f"{name}",
                        f"float{src} -> float{dst} convert inside a "
                        f"scan/while body of {name}: hot-loop compute "
                        f"promoted to f64"))
        elif prim == "pallas_call" and dtype == "float64":
            findings.append(Finding(
                "jaxpr/pallas-on-f64", "error", f"{name}",
                f"pallas_call reachable in the f64 trace of {name}: the "
                f"f32 kernels must be gated out by _pallas_active"))
        elif prim in _TRANSFER_PRIMS and in_loop:
            findings.append(Finding(
                "jaxpr/transfer-in-loop", "error", f"{name}",
                f"{prim} inside a scan/while body of {name}: hidden "
                f"host/device round-trip per iteration"))
        elif prim == "dot_general":
            in_bits = max((_float_bits(v.aval.dtype) for v in eqn.invars),
                          default=0)
            out_bits = max((_float_bits(v.aval.dtype) for v in eqn.outvars),
                           default=0)
            if in_bits and out_bits and out_bits < in_bits:
                findings.append(Finding(
                    "jaxpr/accum-downcast", "error", f"{name}",
                    f"dot_general accumulates float{in_bits} operands "
                    f"into float{out_bits} in {name}"))
            if (expect_full_gemms is not None and in_loop and full_p
                    and any(_float_bits(v.aval.dtype)
                            and full_p in tuple(v.aval.shape)
                            for v in eqn.invars)):
                gemms_in_loop += 1
    if expect_full_gemms is not None and gemms_in_loop != expect_full_gemms:
        findings.append(Finding(
            "jaxpr/full-gemm-count", "error", f"{name}",
            f"{gemms_in_loop} full-X GEMMs inside the sweep loop of "
            f"{name}; the engine contract is exactly {expect_full_gemms} "
            f"(the Lemma-9 certification GEMV) per row"))
    return findings


def lint_traceable(fn, *args, name: str, dtype: str, full_p=None,
                   expect_full_gemms=None) -> list:
    """Trace ``fn(*args)`` and lint the jaxpr (test-fixture entry point)."""
    closed = jax.make_jaxpr(fn)(*args)
    return lint_closed_jaxpr(name, closed, dtype=dtype, full_p=full_p,
                             expect_full_gemms=expect_full_gemms)


# ---------------------------------------------------------------------------
# Representative problem + entry registry
# ---------------------------------------------------------------------------

# all dims distinct so full-p is unambiguous in avals
_N, _P, _PB, _GB, _L, _K = 8, 20, 12, 4, 4, 2
_SIZES = [3, 2, 5, 4, 6]          # G=5, n_max=6, sum=20
_MAX_ITER, _CHECK_EVERY = 60, 10


def _rep(dtype):
    """Tiny representative SGL/NN problem shared by every entry trace."""
    from ..core.groups import GroupSpec

    rng = np.random.default_rng(0)
    spec = GroupSpec.from_sizes(_SIZES)
    X = jnp.asarray(rng.standard_normal((_N, _P)), dtype)
    y = jnp.asarray(rng.standard_normal(_N), dtype)
    S = np.zeros(_P, dtype=bool)
    S[:10] = True                  # groups 0..2 (sizes 3+2+5)
    sub_spec, col_idx = spec.bucketed_subset(S, _PB, _GB)
    X_sub = jnp.zeros((_N, _PB), dtype).at[:, :len(col_idx)].set(
        X[:, col_idx])
    lams = jnp.asarray(np.geomspace(1.0, 0.3, _L), dtype)
    valid = jnp.ones(_L, dtype=bool)
    beta0 = jnp.zeros(_PB, dtype)
    lip = jnp.asarray(4.0, dtype)
    return dict(spec=spec, sub_spec=sub_spec, X=X, y=y, X_sub=X_sub,
                lams=lams, valid=valid, beta0=beta0, lip=lip,
                mu=jnp.asarray(rng.standard_normal(_P) * 0.1, dtype))


def _stackK(a):
    return jnp.stack([a] * _K)


def _fold_rep(dtype):
    r = _rep(dtype)
    from ..core.cv import _stack_specs
    r["Y"] = _stackK(r["y"])
    r["masks"] = jnp.ones((_K, _N), dtype)
    r["sub_specs"] = _stack_specs([r["sub_spec"]] * _K)
    for k in ("X_sub", "lams", "valid", "beta0", "lip", "mu"):
        r[k + "s"] = _stackK(r[k])
    r["gap_scales"] = jnp.ones(_K, dtype)
    return r


def _entries():
    """(name, build(dtype) -> (fn, args), full_p, expect_full_gemms)."""
    from ..core import cv as _cv
    from ..core import dpc as _dpc
    from ..core import screening as _scr
    from ..core import session as _sess
    from ..core.path_engine import sweep_nn_core, sweep_sgl_core
    from ..core.solver import fista_nn_lasso, fista_sgl

    sweep_kw = dict(max_iter=_MAX_ITER, check_every=_CHECK_EVERY,
                    use_pallas=False)

    def sweep_sgl(dtype, centered, loss=None):
        from ..core.losses import get_loss
        r = _rep(dtype)
        kw = dict(sweep_kw)
        if loss is not None:
            kw["loss"] = get_loss(loss)
        fn = functools.partial(sweep_sgl_core, **kw)
        args = [r["X"], r["X_sub"], r["y"], r["spec"], r["sub_spec"], 0.9,
                r["lip"], r["lams"], r["valid"], r["beta0"], 1e-9, 1.0]
        if centered:
            args.append(r["mu"])
        return fn, args

    def sweep_nn(dtype):
        r = _rep(dtype)
        fn = functools.partial(sweep_nn_core, **sweep_kw)
        cols = jnp.arange(r["X_sub"].shape[1], dtype=jnp.int32)
        return fn, [r["X"], r["X_sub"], cols, r["y"], r["lip"], r["lams"],
                    r["valid"], r["beta0"], 1e-9, 1.0]

    def fold_sweep_sgl(dtype, centered):
        r = _fold_rep(dtype)
        axes = _cv._SGL_SWEEP_AXES + ((0,) if centered else ())
        fn = jax.vmap(functools.partial(sweep_sgl_core, **sweep_kw),
                      in_axes=axes)
        args = [r["X"], r["X_subs"], r["Y"], r["spec"], r["sub_specs"], 0.9,
                r["lips"], r["lamss"], r["valids"], r["beta0s"], 1e-9,
                r["gap_scales"]]
        if centered:
            args.append(r["mus"])
        return fn, args

    def fold_sweep_nn(dtype):
        r = _fold_rep(dtype)
        fn = jax.vmap(functools.partial(sweep_nn_core, **sweep_kw),
                      in_axes=_cv._NN_SWEEP_AXES)
        cols = jnp.broadcast_to(
            jnp.arange(r["X_subs"].shape[2], dtype=jnp.int32),
            r["X_subs"].shape[::2])
        return fn, [r["X"], r["X_subs"], cols, r["Y"], r["lips"],
                    r["lamss"], r["valids"], r["beta0s"], 1e-9,
                    r["gap_scales"]]

    def screen_folds_sgl(dtype, centered):
        r = _fold_rep(dtype)
        rem = _stackK(r["lams"])
        vecN = jnp.ones((_K, _N), dtype)
        vecP = jnp.ones((_K, _P), dtype)
        vecG = jnp.ones((_K, len(_SIZES)), dtype)
        ones = jnp.ones(_K, dtype)
        fn = functools.partial(_cv._screen_folds_sgl, screen="gapsafe",
                               use_pallas=False)
        return fn, [r["X"], r["Y"], r["spec"], 0.9, rem, ones, 2.0 * ones,
                    vecN, vecN, vecP, vecP, r["masks"], vecP, vecG, 0.0,
                    r["mus"] if centered else None]

    def screen_folds_nn(dtype):
        r = _fold_rep(dtype)
        rem = _stackK(r["lams"])
        vecN = jnp.ones((_K, _N), dtype)
        vecP = jnp.ones((_K, _P), dtype)
        ones = jnp.ones(_K, dtype)
        fn = functools.partial(_cv._screen_folds_nn, screen="gapsafe",
                               use_pallas=False)
        return fn, [r["X"], r["Y"], rem, ones, 2.0 * ones, vecN, vecN,
                    vecP, vecP, r["masks"], vecP, 0.0]

    def grid_screen_sgl(dtype):
        r = _rep(dtype)
        vecP = jnp.ones(_P, dtype)
        vecG = jnp.ones(len(_SIZES), dtype)
        fn = functools.partial(_scr.tlfre_screen_grid, safety=0.0,
                               use_pallas=False)
        return fn, [r["X"], r["y"], r["spec"], 0.9, r["lams"], 1.0,
                    r["y"], r["y"], vecP, vecG]

    def grid_screen_sgl_gapsafe(dtype):
        r = _rep(dtype)
        vecP = jnp.ones(_P, dtype)
        vecG = jnp.ones(len(_SIZES), dtype)
        radii = jnp.ones(_L, dtype)

        def both(spec, alpha, c_prev, radii, col_n, gspec, y, rem, tb,
                 resid, pen):
            radii = _scr.gap_safe_grid_radii(y, rem, tb, resid, pen)
            return _scr.gap_safe_screen_grid(spec, alpha, c_prev, radii,
                                             col_n, gspec, use_pallas=False)

        return both, [r["spec"], 0.9, vecP, radii, vecP, vecG, r["y"],
                      r["lams"], r["y"], r["y"], jnp.asarray(1.0, dtype)]

    def grid_screen_nn(dtype):
        r = _rep(dtype)
        vecP = jnp.ones(_P, dtype)
        fn = functools.partial(_dpc.dpc_screen_grid, safety=0.0)
        return fn, [r["X"], r["y"], r["lams"], r["y"], r["y"], vecP]

    def fold_duals_sgl(dtype):
        r = _fold_rep(dtype)
        betas = jnp.zeros((_K, _P), dtype)
        return (lambda *a: _sess._fold_duals_sgl(*a, None)), [
            r["X"], r["spec"], 0.9, r["Y"], r["masks"], betas, 1.0]

    def fold_duals_nn(dtype):
        r = _fold_rep(dtype)
        betas = jnp.zeros((_K, _P), dtype)
        return _sess._fold_duals_nn, [r["X"], r["Y"], r["masks"], betas,
                                      1.0]

    def fista_sgl_entry(dtype, loss=None):
        from ..core.losses import get_loss
        r = _rep(dtype)
        kw = dict(max_iter=_MAX_ITER, check_every=_CHECK_EVERY, tol=1e-9)
        if loss is not None:
            kw["loss"] = get_loss(loss)
        fn = functools.partial(fista_sgl, **kw)
        return fn, [r["X_sub"], r["y"], r["sub_spec"], 0.5, 0.9, r["lip"],
                    r["beta0"]]

    def grid_radii_logistic(dtype):
        from ..core.losses import LOGISTIC
        r = _rep(dtype)
        fit = jnp.zeros(_N, dtype)
        resid = LOGISTIC.residual(r["y"], fit)
        fn = functools.partial(_scr.gap_safe_grid_radii_loss, LOGISTIC)
        return fn, [r["y"], r["lams"], r["y"], fit, resid,
                    jnp.asarray(1.0, dtype)]

    def fista_nn_entry(dtype):
        r = _rep(dtype)
        fn = functools.partial(fista_nn_lasso, max_iter=_MAX_ITER,
                               check_every=_CHECK_EVERY, tol=1e-9)
        return fn, [r["X_sub"], r["y"], 0.5, r["lip"], r["beta0"]]

    def serve_lambda_max(dtype, penalty):
        from ..launch.sgl_serve import _batch_lambda_max
        r = _rep(dtype)
        ys = _stackK(r["y"])
        spec = r["spec"] if penalty == "sgl" else None
        fn = functools.partial(_batch_lambda_max, penalty=penalty)
        return fn, [r["X"], ys, spec, 0.9]

    def serve_refit(dtype, penalty):
        from ..launch.sgl_serve import _batch_refit
        r = _rep(dtype)
        ys = _stackK(r["y"])
        lams = jnp.asarray([0.5, 0.4], dtype)
        spec = r["spec"] if penalty == "sgl" else None
        fn = functools.partial(_batch_refit, penalty=penalty,
                               max_iter=_MAX_ITER,
                               check_every=_CHECK_EVERY)
        return fn, [r["X"], ys, lams, spec, 0.9, r["lip"], 1e-9]

    return [
        ("sweep_sgl", lambda d: sweep_sgl(d, False), _P, 1),
        ("sweep_sgl_centered", lambda d: sweep_sgl(d, True), _P, 1),
        ("sweep_sgl_logistic",
         lambda d: sweep_sgl(d, False, loss="logistic"), _P, 1),
        ("sweep_nn", sweep_nn, _P, 1),
        ("fold_sweep_sgl", lambda d: fold_sweep_sgl(d, False), _P, 1),
        ("fold_sweep_sgl_centered", lambda d: fold_sweep_sgl(d, True),
         _P, 1),
        ("fold_sweep_nn", fold_sweep_nn, _P, 1),
        ("screen_folds_sgl", lambda d: screen_folds_sgl(d, False),
         _P, None),
        ("screen_folds_sgl_centered", lambda d: screen_folds_sgl(d, True),
         _P, None),
        ("screen_folds_nn", screen_folds_nn, _P, None),
        ("grid_screen_sgl", grid_screen_sgl, _P, None),
        ("grid_screen_sgl_gapsafe", grid_screen_sgl_gapsafe, _P, None),
        ("grid_screen_nn", grid_screen_nn, _P, None),
        ("fold_duals_sgl", fold_duals_sgl, _P, None),
        ("fold_duals_nn", fold_duals_nn, _P, None),
        ("fista_sgl", fista_sgl_entry, _P, None),
        ("fista_sgl_logistic",
         lambda d: fista_sgl_entry(d, loss="logistic"), _P, None),
        ("fista_nn", fista_nn_entry, _P, None),
        ("grid_radii_logistic", grid_radii_logistic, _P, None),
        ("serve_lambda_max_sgl", lambda d: serve_lambda_max(d, "sgl"),
         _P, None),
        ("serve_lambda_max_nn", lambda d: serve_lambda_max(d, "nn_lasso"),
         _P, None),
        ("serve_refit_sgl", lambda d: serve_refit(d, "sgl"), _P, None),
        ("serve_refit_nn", lambda d: serve_refit(d, "nn_lasso"), _P, None),
    ]


def entry_names() -> list:
    return [name for name, _, _, _ in _entries()]


def run(dtypes=("float32", "float64"), entries=None) -> list:
    """Trace every registered entry at the given dtypes and lint.

    f64 traces check the exactness contract (no downcasts, no kernels);
    f32 traces check the hot-loop contract (no upcasts).  Requires x64 to
    be enabled (``repro.analysis`` enables it on import).
    """
    findings = []
    only = set(entries) if entries is not None else None
    for name, build, full_p, expect in _entries():
        if only is not None and name not in only:
            continue
        for dt in dtypes:
            fn, args = build(jnp.dtype(dt))
            closed = jax.make_jaxpr(fn)(*args)
            findings.extend(lint_closed_jaxpr(
                f"{name}[{dt}]", closed, dtype=dt, full_p=full_p,
                expect_full_gemms=expect))
    return findings
