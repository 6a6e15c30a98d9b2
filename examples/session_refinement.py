"""Two-stage model selection on a persistent SGLSession, plus the serving
front-end — the Problem/Plan/Session quickstart.

One declarative surface over path, CV, and serving:

  1. Build an immutable ``Problem`` and a declarative ``Plan``.
  2. ``session.cv(plan)``: fold-batched K-fold CV on a coarse grid.
  3. ``session.refine(factor=10)``: a finer grid around the selected
     lambda, seeded from the coarse run's certified per-fold duals and
     reusing the session's compiled buckets — same answer as an
     exhaustive fine-grid CV, warm.
  4. ``SGLServer``: queue (X, y, groups) jobs; same-design jobs stack
     their CV folds into ONE fold-batched engine call, and every job
     shares the server's compile cache.

    PYTHONPATH=src python examples/session_refinement.py
"""
import time

import numpy as np

from repro.core import GroupSpec, Plan, Problem, SGLSession

# --- a synthetic problem with a real bias/variance tradeoff ---------------
rng = np.random.default_rng(0)
N, G, n = 150, 60, 5
p = G * n
X = rng.standard_normal((N, p))
beta_true = np.zeros(p)
for g in rng.choice(G, 6, replace=False):
    beta_true[g * n + rng.choice(n, 2, replace=False)] = rng.standard_normal(2)
y = X @ beta_true + 1.5 * rng.standard_normal(N)

problem = Problem.sgl(X, y, groups=GroupSpec.uniform_groups(G, n))
plan = Plan(alpha=1.0, n_lambdas=24, n_folds=3, tol=3e-6, safety=1e-6,
            max_iter=8000, check_every=50)
session = SGLSession(problem, plan)

# --- stage 1: coarse CV ----------------------------------------------------
t0 = time.perf_counter()
coarse = session.cv()
t_coarse = time.perf_counter() - t0
print(f"coarse grid : {len(coarse.lambdas)} lambdas in {t_coarse:.2f}s, "
      f"best lambda/lam_max = {coarse.best_lambda / coarse.lam_max:.4f}, "
      f"compilations = {coarse.stats.n_compilations}")

# --- stage 2: warm refinement around the selection -------------------------
t0 = time.perf_counter()
ref = session.refine(factor=10.0)
t_ref = time.perf_counter() - t0
print(f"refinement  : {len(ref.fine.lambdas)} lambdas spanning 10x around "
      f"{coarse.best_lambda:.4f} in {t_ref:.2f}s")
print(f"  selected lambda       : {ref.lambda_:.4f} "
      f"(coarse pick was {coarse.best_lambda:.4f})")
print(f"  warm-start reference  : {ref.warm_start_lambda:.4f} "
      f"(coarse certified duals)")
print(f"  new sweep compilations: {ref.new_compilations} "
      f"(bucket shapes not already compiled by the coarse run)")
print(f"  total FISTA iterations: {ref.total_iters}")

# cold comparison: the same fine grid on a fresh session
cold_session = SGLSession(problem)
t0 = time.perf_counter()
cold = cold_session.cv(plan.with_(lambdas=ref.fine.lambdas))
t_cold = time.perf_counter() - t0
agree = np.max(np.abs(ref.fine.fold_betas - cold.fold_betas))
print(f"cold fine CV: {t_cold:.2f}s, {int(cold.fold_iters.sum())} FISTA "
      f"iterations, {cold.stats.n_compilations} compilations")
print(f"  warm == cold to {agree:.2e}; same selection: "
      f"{ref.lambda_ == cold.best_lambda}")

# --- model-selection-as-a-service ------------------------------------------
from repro.launch.sgl_serve import SGLServer, raise_on_errors

server = SGLServer(Plan(n_folds=3, n_lambdas=16, tol=1e-6, safety=1e-6,
                        max_iter=6000, check_every=50))
# three responses over ONE shared design -> their 3x3 CV folds run as one
# fold-stacked engine call; a second design runs separately but shares the
# compile cache
for X_job in (X, X):
    yb = X_job @ beta_true + 0.5 * rng.standard_normal(N)
    server.submit(X_job, yb, groups=[n] * G)
server.submit(rng.standard_normal((N, p)), y, groups=[n] * G)
t0 = time.perf_counter()
results = raise_on_errors(server.drain())
t_serve = time.perf_counter() - t0
print(f"\nserve       : {len(results)} jobs in {t_serve:.2f}s "
      f"({t_serve / len(results) * 1e3:.0f}ms/job)")
for jid, r in sorted(results.items()):
    print(f"  job {jid}: best_lambda={r.best_lambda:.4f} "
          f"nnz={int(np.sum(np.abs(r.coef) > 1e-8))} "
          f"batched_with={r.batched_with} "
          f"latency={r.latency * 1e3:.0f}ms")
