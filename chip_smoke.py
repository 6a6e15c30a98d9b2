#!/usr/bin/env python3
"""On-chip smoke test: the certified TLFre / DPC path, CV and Pallas kernels.

Drives the main path once through the entry points a user calls
(``Problem`` / ``Plan`` / ``SGLSession``) on float32 data drawn from
``--seed``, and checks every returned solution against an independent
float64 duality gap computed here in numpy over ALL of X (so a feature the
screening rules wrongly discarded would show up as an uncertified row).

Phases (one chip, the default):

* ``precision`` — errors of the screening GEMM, the certification GEMV and
  the ``xtv`` kernel against float64 numpy at the ADNI shape.
* ``sgl_path``  — ADNI shape of the paper's Table 2 (N=747, p=426,040,
  ragged gene groups of ~4.5 SNPs), Synthetic-1 design: a 20-point path
  from lambda_max down to 0.1 lambda_max, cold then warm, on one session.
* ``sgl_cv``    — 5-fold CV on the same session (fold-stack kernels).
* ``dpc_path`` / ``dpc_cv`` — nonnegative Lasso (DPC) on the 1024 x 11,553
  image-dictionary stand-in of Table 3: a 20-point path down to 0.2
  lambda_max (cold, warm), then 5-fold CV.

``--chips 4`` runs only the feature-sharded SGL path (``feature_shards=4``
on a real 4-device 'feature' mesh) and the same problem unsharded in the
same process, on an 8-point grid down to 0.3 lambda_max, and checks that
kept sets and betas agree.

Every phase prints one JSON line; the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any failure exits non-zero without that line, and so does a run where JAX
finds no TPU or that is started outside a checkout of this repository.

Usage::

    python chip_smoke.py [--seed 0]
    python chip_smoke.py --chips 4 [--seed 0]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent

ADNI_N, ADNI_P, ADNI_AVG_GROUP = 747, 426_040, 4.5
DPC_N, DPC_P = 1024, 11_553
N_LAMBDAS, TOL, N_FOLDS = 20, 1e-5, 5
# Grid floors (fractions of lambda_max), cut from the default 0.01 to what
# FISTA certifies at TOL within the default max_iter on one v5e.  SGL: to
# 0.01 a cold path did not finish in 30 minutes (below ~0.3, at p/N = 570,
# TLFre keeps every feature and a full-design FISTA iteration takes
# ~15 ms).  DPC: on the near-collinear image dictionary a 0.1 floor left 9
# of 20 rows, and 0.01 left 18, at max_iter with gaps 7x and 17x tol; to
# 0.2 every path and fold row certifies (<= 6,620 iterations).
SGL_MIN_RATIO, DPC_MIN_RATIO = 0.1, 0.2
FOUR_CHIP_LAMBDAS, FOUR_CHIP_MIN_RATIO = 8, 0.3
GAP_SLACK = 2.0          # a row passes when its f64 gap <= 2 * tol * scale
F32_FLOOR = 64 * float(np.finfo(np.float32).eps)   # certificate floor


class SmokeFailure(RuntimeError):
    pass


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


# ---------------------------------------------------------------------------
# Data (generated on the host from the seed; loading counts as set-up)
# ---------------------------------------------------------------------------

def adni_problem(seed: int, p: int = ADNI_P, n: int = ADNI_N,
                 group_multiple: int = 1):
    """Synthetic-1 design at the ADNI shape: iid N(0,1) X, ragged gene
    groups, beta* on 10% of groups with 20% of each group's features,
    y = X beta* + 0.01 eps.  ``group_multiple`` drops trailing groups so
    the group count divides it (a group-aligned feature partition)."""
    from benchmarks.data_synth import ragged_sizes
    sizes = np.asarray(ragged_sizes(p, ADNI_AVG_GROUP, seed))
    sizes = sizes[:len(sizes) - len(sizes) % group_multiple]
    p = int(sizes.sum())
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, p), dtype=np.float32)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    beta = np.zeros(p)
    for g in rng.choice(len(sizes), max(1, len(sizes) // 10), replace=False):
        k = max(1, int(round(0.2 * sizes[g])))
        idx = starts[g] + rng.choice(sizes[g], k, replace=False)
        beta[idx] = rng.standard_normal(k)
    nz = np.nonzero(beta)[0]
    y = X[:, nz].astype(np.float64) @ beta[nz] + 0.01 * rng.standard_normal(n)
    return X, y.astype(np.float32), sizes


# ---------------------------------------------------------------------------
# Independent float64 reference: full-problem duality gaps
# ---------------------------------------------------------------------------

def _fit(X64, B):
    """X @ b for every row b of B, touching only the columns B uses."""
    nz = np.nonzero(np.any(B != 0.0, axis=0))[0]
    return B[:, nz] @ X64[:, nz].T


def sgl_gaps(X64, y64, sizes, alpha, lams, B):
    """f64 duality gaps of SGL solutions B (J, p) at lams (J,).

    Primal ``0.5||y - Xb||^2 + lam (alpha sum_g sqrt(n_g)||b_g|| + ||b||_1)``;
    the dual point is the residual over lam, scaled by the largest
    s in (0, 1] with ``||S_1(s X_g^T r / lam)||_2 <= alpha sqrt(n_g)`` for
    every group (found by bisection, so it is feasible)."""
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    w = alpha * np.sqrt(sizes)
    R = y64[None, :] - _fit(X64, B)                       # (J, N)
    A = np.abs((R / lams[:, None]) @ X64)                 # (J, p)

    def feasible(s):
        sh = np.maximum(s[:, None] * A - 1.0, 0.0)
        return np.all(np.sqrt(np.add.reduceat(sh * sh, starts, axis=1))
                      <= w[None, :], axis=1)

    lo, hi = np.zeros(len(lams)), np.ones(len(lams))
    done = feasible(hi)                   # s = 1 already feasible
    for _ in range(60):
        mid = np.where(done, 1.0, 0.5 * (lo + hi))
        ok = feasible(mid)
        lo, hi = np.where(ok, mid, lo), np.where(ok, hi, mid)
    theta = lo[:, None] * R / lams[:, None]
    gnorm = np.sqrt(np.add.reduceat(B * B, starts, axis=1))
    pen = gnorm @ w + np.abs(B).sum(axis=1)
    primal = 0.5 * np.sum(R * R, axis=1) + lams * pen
    d = y64[None, :] - lams[:, None] * theta
    dual = 0.5 * (y64 @ y64) - 0.5 * np.sum(d * d, axis=1)
    return primal - dual


def nn_gaps(X64, y64, lams, B):
    """f64 duality gaps of nonnegative-Lasso solutions B (J, p): dual point
    r / lam scaled into ``{theta : X^T theta <= 1}``."""
    _check(bool(np.all(B >= 0.0)), "nonnegative Lasso returned a negative "
           "coefficient")
    R = y64[None, :] - _fit(X64, B)
    m = np.max((R / lams[:, None]) @ X64, axis=1)
    s = np.where(m > 1.0, 1.0 / m, 1.0)
    theta = s[:, None] * R / lams[:, None]
    primal = 0.5 * np.sum(R * R, axis=1) + lams * B.sum(axis=1)
    d = y64[None, :] - lams[:, None] * theta
    dual = 0.5 * (y64 @ y64) - 0.5 * np.sum(d * d, axis=1)
    return primal - dual


def worst_gap_ratio(gaps, y64, tol):
    """max over rows of gap / (tol * 0.5||y||^2) — the engine's scale."""
    _check(bool(np.all(np.isfinite(gaps))), "non-finite duality gap")
    return float(np.max(gaps) / (tol * max(0.5 * float(y64 @ y64), 1e-30)))


def certify_path(X64, y64, res, tol, sizes=None, alpha=1.0):
    _check(bool(np.all(np.isfinite(res.betas))), "non-finite path betas")
    B = np.asarray(res.betas, dtype=np.float64)
    lams = np.asarray(res.lambdas, dtype=np.float64)
    gaps = (sgl_gaps(X64, y64, sizes, alpha, lams, B) if sizes is not None
            else nn_gaps(X64, y64, lams, B))
    return worst_gap_ratio(gaps, y64, tol)


def certify_cv(X64, y64, cv, tol, sizes=None, alpha=1.0):
    """Each fold's rows against that fold's own training problem."""
    _check(bool(np.all(np.isfinite(cv.fold_betas))), "non-finite CV betas")
    lams = np.asarray(cv.lambdas, dtype=np.float64)
    worst = 0.0
    for k, (train, _) in enumerate(cv.folds):
        Xk, yk = X64[train], y64[train]
        B = np.asarray(cv.fold_betas[k], dtype=np.float64)
        gaps = (sgl_gaps(Xk, yk, sizes, alpha, lams, B) if sizes is not None
                else nn_gaps(Xk, yk, lams, B))
        worst = max(worst, worst_gap_ratio(gaps, yk, tol))
    return worst


# ---------------------------------------------------------------------------
# Device-side evidence
# ---------------------------------------------------------------------------

def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def kernels_compiled(X, spec, K: int, L: int, p_dpc: int) -> dict:
    """Lower every kernel wrapper at the smoke's shapes and check each one
    becomes a compiled TPU custom call (not the interpreter)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    _check(ops._interpret_default() is False,
           "the Pallas kernels would run in interpret mode")
    f32 = jnp.float32
    n_max, G = spec.max_size, spec.num_groups

    def s(*shape, dt=f32):
        return jax.ShapeDtypeStruct(shape, dt)

    mask = s(n_max, G, dt=jnp.bool_)
    lowered = {
        "xtv": ops.xtv.lower(X, s(X.shape[0])),
        "screen_norms_batched": ops.screen_norms_batched.lower(
            s(n_max, L, G), mask),
        "screen_norms_folds": ops.screen_norms_folds.lower(
            s(n_max, K, L, G), mask),
        "sgl_prox_padded": ops.sgl_prox_padded.lower(
            s(n_max, G), mask, s(), s(G)),
        "dpc_screen_folds": ops.dpc_screen_folds.lower(
            s(K, L, p_dpc), s(K, L), s(K, p_dpc)),
    }
    found = {k: "tpu_custom_call" in v.as_text() for k, v in lowered.items()}
    _check(all(found.values()), f"kernels not lowered to TPU custom calls: "
           f"{found}")
    return found


def precision_phase(X, X64, seed: int) -> dict:
    """Max error, relative to |A||B|, of the engine's screening GEMM and
    certification GEMV (``core.linalg.mm``), the ``xtv`` kernel, and (for
    contrast) a default-precision GEMM, against float64 numpy."""
    import jax.numpy as jnp
    from repro.core.linalg import mm
    from repro.kernels import ops
    rng = np.random.default_rng(seed + 1)
    N = X.shape[0]
    centers = rng.standard_normal((8, N)).astype(np.float32)
    v = centers[0]
    c64 = centers.astype(np.float64)
    ref_gemm, scale_gemm = c64 @ X64, np.abs(c64) @ np.abs(X64)
    ref_gemv, scale_gemv = ref_gemm[0], scale_gemm[0]
    Xd, Cd = X, jnp.asarray(centers)

    def err(got, ref, scale):
        return float(np.max(np.abs(np.asarray(got, np.float64) - ref)
                            / np.maximum(scale, 1e-30)))

    out = {
        "screening_gemm_highest": err(mm(Cd, Xd), ref_gemm, scale_gemm),
        "certification_gemv_highest": err(mm(Xd.T, Cd[0]), ref_gemv,
                                          scale_gemv),
        "xtv_kernel": err(ops.xtv(Xd, Cd[0]), ref_gemv, scale_gemv),
        "screening_gemm_default": err(Cd @ Xd, ref_gemm, scale_gemm),
    }
    for k in ("screening_gemm_highest", "certification_gemv_highest",
              "xtv_kernel"):
        _check(out[k] <= F32_FLOOR, f"{k} error {out[k]:.3g} exceeds the "
               f"float32 certificate floor {F32_FLOOR:.3g}")
    return out


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def _stats(st) -> dict:
    return {"n_compilations": st.n_compilations, "n_segments": st.n_segments,
            "n_screens": st.n_screens, "n_rejected": st.n_rejected,
            "n_uncertified": st.n_uncertified,
            "n_pallas_screens": st.n_pallas_screens}


def _rejection(kept, p) -> dict:
    r = 1.0 - np.asarray(kept, dtype=np.float64) / p
    return {"rejection_ratio_mean": float(r.mean()),
            "rejection_ratio_min": float(r.min())}


class CompileClock:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit reports its short retrieval as the
    compile)."""

    def __init__(self):
        import jax.monitoring as mon
        self.secs, self.hits = 0.0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


CLOCK = None          # the process's CompileClock, set by main()


def timed(fn):
    """(fn(), wall seconds, backend-compile seconds among them)."""
    c0, t0 = CLOCK.secs, time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0, CLOCK.secs - c0


def path_phase(name, session, plan, X64, y64, device, sizes=None) -> dict:
    """Cold then warm ``session.path(plan)``; both certified in f64."""
    cold, t_cold, c_cold = timed(lambda: session.path(plan))
    warm, t_warm, c_warm = timed(lambda: session.path(plan))
    p = X64.shape[1]
    rec = {"phase": name, "cold_s": t_cold, "warm_s": t_warm,
           "cold_compile_s": c_cold, "warm_compile_s": c_warm,
           "cold": _stats(cold.stats), "warm": _stats(warm.stats),
           **_rejection(cold.kept_features, p),
           "peak_bytes_in_use": peak_bytes(device)}
    rec["worst_gap_over_tol"] = max(
        certify_path(X64, y64, r, plan.tol, sizes, plan.alpha)
        for r in (cold, warm))
    _emit(rec)
    _check(rec["worst_gap_over_tol"] <= GAP_SLACK,
           f"{name}: a row is not certified by the f64 gap "
           f"({rec['worst_gap_over_tol']:.3g} x tol)")
    _check(cold.stats.n_uncertified == 0 and warm.stats.n_uncertified == 0,
           f"{name}: the engine returned rows it could not certify")
    _check(cold.stats.n_pallas_screens > 0
           and warm.stats.n_pallas_screens > 0,
           f"{name}: no grid screen ran through the Pallas kernels")
    return rec


def cv_phase(name, session, plan, X64, y64, device, sizes=None) -> dict:
    cv, t, c = timed(lambda: session.cv(plan))
    rec = {"phase": name, "cold_s": t, "cold_compile_s": c,
           **_stats(cv.stats),
           **_rejection(cv.kept_features, X64.shape[1]),
           "best_lambda": cv.best_lambda,
           "peak_bytes_in_use": peak_bytes(device)}
    rec["worst_gap_over_tol"] = certify_cv(X64, y64, cv, plan.tol, sizes,
                                           plan.alpha)
    _emit(rec)
    _check(rec["worst_gap_over_tol"] <= GAP_SLACK,
           f"{name}: a fold row is not certified by the f64 gap "
           f"({rec['worst_gap_over_tol']:.3g} x tol)")
    _check(cv.stats.n_uncertified == 0,
           f"{name}: the engine returned fold rows it could not certify")
    _check(cv.stats.n_pallas_screens > 0,
           f"{name}: no fold screen ran through the Pallas kernels")
    return rec


def run_one_chip(seed: int, device) -> None:
    sgl_phases(seed, device)
    dpc_phases(seed, device)


def sgl_phases(seed: int, device) -> None:
    """Kernel lowering, matmul precision, then the SGL path and CV."""
    from repro.core import Plan, Problem, SGLSession

    (X_np, y_np, sizes), t_data, _ = timed(lambda: adni_problem(seed))
    X64, y64 = X_np.astype(np.float64), y_np.astype(np.float64)
    prob = Problem.sgl(X_np, y_np, groups=sizes.tolist())
    session = SGLSession(prob)
    _emit({"phase": "setup", "data_s": t_data, "N": X_np.shape[0],
           "p": X_np.shape[1], "G": len(sizes), "n_max": int(sizes.max()),
           "x_bytes": int(X_np.nbytes)})

    _emit({"phase": "kernels_compiled",
           **kernels_compiled(prob.X, prob.spec, N_FOLDS, 32, DPC_P)})
    prec = precision_phase(prob.X, X64, seed)
    _emit({"phase": "precision", "floor": F32_FLOOR, **prec})

    plan = Plan(alpha=1.0, n_lambdas=N_LAMBDAS, min_ratio=SGL_MIN_RATIO,
                tol=TOL)
    path_phase("sgl_path", session, plan, X64, y64, device, sizes)
    cv_phase("sgl_cv", session, plan.with_(n_folds=N_FOLDS), X64, y64,
             device, sizes)


def dpc_phases(seed: int, device) -> None:
    """The nonnegative-Lasso (DPC) path and CV."""
    import jax.numpy as jnp
    from benchmarks.data_synth import image_like
    from repro.core import Plan, Problem, SGLSession

    Xd_np, yd_np = image_like(DPC_N, DPC_P, seed)
    Xd64, yd64 = Xd_np.astype(np.float64), yd_np.astype(np.float64)
    dsess = SGLSession(Problem.nn_lasso(jnp.asarray(Xd_np),
                                        jnp.asarray(yd_np)))
    dplan = Plan(n_lambdas=N_LAMBDAS, min_ratio=DPC_MIN_RATIO, tol=TOL)
    path_phase("dpc_path", dsess, dplan, Xd64, yd64, device)
    cv_phase("dpc_cv", dsess, dplan.with_(n_folds=N_FOLDS), Xd64, yd64,
             device)


def run_four_chips(seed: int, devices) -> None:
    """Feature-sharded SGL path on a real 4-device mesh vs the unsharded
    engine on the same problem, in one process."""
    from repro.core import Plan, Problem, SGLSession
    from repro.distributed.feature_shard import (feature_ops,
                                                 plan_feature_shards,
                                                 resolve_feature_mesh)
    _check(len(devices) >= 4, f"--chips 4 needs 4 devices, JAX found "
           f"{len(devices)}")
    (X_np, y_np, sizes), t_data, _ = timed(
        lambda: adni_problem(seed, group_multiple=4))
    X64, y64 = X_np.astype(np.float64), y_np.astype(np.float64)
    prob = Problem.sgl(X_np, y_np, groups=sizes.tolist())
    _emit({"phase": "setup", "data_s": t_data, "N": X_np.shape[0],
           "p": X_np.shape[1], "G": len(sizes), "n_max": int(sizes.max())})

    fplan = plan_feature_shards(4, X_np.shape[1], prob.spec)
    _check(fplan.n_shards == 4, f"partition degraded to {fplan.n_shards}")
    mesh = resolve_feature_mesh(4)
    _check(mesh is not None, "no 4-device 'feature' mesh")
    Xs = feature_ops(4, mesh).place(fplan.stack_columns(X_np))
    shard_devs = sorted({sh.device.id for sh in Xs.addressable_shards})
    _check(len(shard_devs) == 4, f"X's shards sit on devices {shard_devs}")
    del Xs

    # the sharded screens run the jnp rules; so does the reference here, so
    # the two differ only in where X lives.  A short grid keeps a call on
    # four chips (four times the chip time) to a few minutes.
    plan = Plan(alpha=1.0, n_lambdas=FOUR_CHIP_LAMBDAS,
                min_ratio=FOUR_CHIP_MIN_RATIO, tol=TOL, use_pallas=False)
    one = SGLSession(prob)
    four = SGLSession(prob)
    r1, t1, c1 = timed(lambda: one.path(plan))
    _emit({"phase": "unsharded_path", "cold_s": t1, "cold_compile_s": c1,
           **_stats(r1.stats), "peak_bytes_in_use": peak_bytes(devices[0])})
    r4, t4, c4 = timed(lambda: four.path(plan.with_(feature_shards=4)))
    b1, b4 = np.asarray(r1.betas), np.asarray(r4.betas)
    rec = {"phase": "feature_shard_path", "shards": 4,
           "x_shard_devices": shard_devs,
           "unsharded_s": t1, "sharded_cold_s": t4,
           "sharded_cold_compile_s": c4,
           "unsharded": _stats(r1.stats), "sharded": _stats(r4.stats),
           "kept_features_equal": bool(np.array_equal(r1.kept_features,
                                                      r4.kept_features)),
           "kept_groups_equal": bool(np.array_equal(r1.kept_groups,
                                                    r4.kept_groups)),
           "support_equal": bool(np.array_equal(b1 != 0, b4 != 0)),
           "max_abs_beta_diff": float(np.max(np.abs(b1 - b4))),
           "max_abs_beta": float(np.max(np.abs(b1))),
           "peak_bytes_in_use": [peak_bytes(d) for d in devices[:4]]}
    rec["worst_gap_over_tol"] = max(
        certify_path(X64, y64, r, plan.tol, sizes, plan.alpha)
        for r in (r1, r4))
    _emit(rec)
    _check(r1.stats.n_uncertified == 0 and r4.stats.n_uncertified == 0,
           "the engine returned rows it could not certify")
    _check(rec["kept_features_equal"] and rec["kept_groups_equal"],
           "sharded kept sets differ from the single-device engine")
    _check(rec["max_abs_beta_diff"] <= 1e-4 * max(rec["max_abs_beta"], 1.0),
           "sharded betas differ from the single-device engine beyond "
           "float32 tolerance")
    _check(rec["worst_gap_over_tol"] <= GAP_SLACK,
           "a sharded or unsharded row is not certified by the f64 gap")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the feature-sharded path on 4 devices")
    args = ap.parse_args(argv)

    for sub in ("src", ""):
        sys.path.insert(0, str(REPO / sub))
    try:
        import repro.core  # noqa: F401
        import benchmarks.data_synth  # noqa: F401
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as exc:
        print(f"chip_smoke.py must run from a checkout of the repository: "
              f"{exc}", file=sys.stderr)
        return 2
    import jax
    cache = enable_compile_cache()
    global CLOCK
    CLOCK = CompileClock()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    _emit({"phase": "device", "platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices),
           "compile_cache": cache})
    try:
        _check(not jax.config.jax_enable_x64, "x64 is on; the smoke runs "
               "the float32 path")
        if args.chips == 4:
            run_four_chips(args.seed, devices)
        else:
            run_one_chip(args.seed, devices[0])
        _check(not jax.config.jax_enable_x64, "an import turned x64 on")
    except SmokeFailure as exc:
        print(f"chip_smoke.py FAILED: {exc}", file=sys.stderr)
        return 1
    _emit({"phase": "compile", "backend_compile_s": CLOCK.secs,
           "persistent_cache_hits": CLOCK.hits})
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
