"""Benchmark harness entry: one function per paper table/figure.

Usage::

    python -m benchmarks.run [SUITE_FILTER] [--suite NAME]
                             [--engine {legacy,batched}] [--folds K]
                             [--smoke]

Runs on a TPU only: it exits with an error when JAX finds no TPU.
Prints ``name,us_per_call,derived`` CSV.  ``derived`` is the headline metric
of the corresponding table (speedup x, rejection ratio).

``--engine`` selects the lambda-path driver used by the path suites
(table1/table2/table3): ``legacy`` (default) is the paper-protocol
per-lambda driver; ``batched`` is the device-resident engine
(``core/path_engine.py``) — grid screening, speculative bucketed sweeps in
a single ``lax.scan`` per segment, in-scan certification, O(log p) solver
compilations.  The ``engine`` suite always benchmarks both drivers against
each other and reports the engine's host-sync / compilation counters.

``--folds`` sets the fold count of the ``cv`` suite (default 5), which
benchmarks the fold-batched ``sgl_cv`` (one stacked screening GEMM per
segment) against K sequential per-fold path solves.

``--suite NAME`` filters to one suite by name (equivalent to the
positional SUITE_FILTER).  The ``session`` suite benchmarks the
Problem/Plan/Session warm two-stage refinement (``session.refine``: coarse
CV, then a fine grid seeded from the coarse certified duals on the same
session) against a cold fine-grid CV — the model-selection serving regime.
The ``cv-pallas`` suite compares elastic vs lockstep fold scheduling and
the fused fold-stack Pallas screening vs the jnp fallback at float32.

``--smoke`` runs only the fast engine + cv + cv-pallas + session +
compile-audit + resource-audit + feature-shard comparison suites at
reduced dimensions.  The three audit suites below check invariants, not
timings; ``tests/test_bench_gates.py`` runs them on the CPU.  The ``feature-shard``
suite (also in the full run) raises if ``Plan(feature_shards=8)`` kept
sets / betas drift from the single-device engine or if the sharded
collective plan is anything but the single partial-fit psum.  The ``compile-audit`` suite (also in the
full run) raises if the engine pays any jit compile key that
``repro.analysis.compile_audit.predict_keys`` did not statically predict.
The ``resource-audit`` suite AOT-compiles the dominating path/fold keys
and raises if XLA's measured peak allocation or FLOP count exceeds the
static cost-card envelope (``repro.analysis.resource_audit``) or a fold
sweep body fires a collective — the soundness gate behind
``analysis/budgets.json`` and ``python -m repro.analysis --capacity``.

REPRO_BENCH_FULL=1 switches to the paper's full dimensions.
"""
from __future__ import annotations

import functools
import sys
import traceback


def _pop_flag(argv, name, default=None, has_value=True):
    for i, a in enumerate(argv):
        if a == name:
            if not has_value:
                del argv[i]
                return True
            if i + 1 >= len(argv):
                raise SystemExit(f"{name} requires a value")
            v = argv[i + 1]
            del argv[i:i + 2]
            return v
        if has_value and a.startswith(name + "="):
            v = a.split("=", 1)[1]
            del argv[i]
            return v
    return default


def main() -> None:
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # a timing from XLA:CPU or the Pallas interpreter is no measurement
        # of this system; refuse rather than print one
        raise SystemExit(f"benchmarks.run measures the TPU; JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    from . import paper_tables
    argv = sys.argv[1:]
    engine = _pop_flag(argv, "--engine", "legacy")
    folds = int(_pop_flag(argv, "--folds", "5"))
    suite_flag = _pop_flag(argv, "--suite", None)
    smoke = _pop_flag(argv, "--smoke", False, has_value=False)
    if engine not in ("legacy", "batched"):
        raise SystemExit(f"unknown --engine {engine!r}")
    if smoke:
        # CI perf-regression gate: fast engine + fold-batched CV + session
        # refinement comparisons
        paper_tables.SGL_DIMS = dict(N=120, G=60, n=5)
        paper_tables.N_LAMBDA = 16
        suites = [
            ("engine", paper_tables.engine_bench),
            ("cv", functools.partial(paper_tables.cv_bench, engine="batched",
                                     n_folds=min(folds, 3))),
            ("cv-pallas", functools.partial(paper_tables.cv_pallas_bench,
                                            n_folds=min(folds, 3))),
            ("session", functools.partial(paper_tables.session_bench,
                                          n_folds=min(folds, 3))),
            ("loss-logistic", paper_tables.loss_logistic_bench),
            # LAST: these import repro.analysis, which enables x64
            # process-wide
            ("compile-audit",
             functools.partial(paper_tables.compile_audit_bench,
                               n_folds=min(folds, 3))),
            ("resource-audit",
             functools.partial(paper_tables.resource_audit_bench,
                               n_folds=min(folds, 3))),
            ("feature-shard", paper_tables.feature_shard_bench),
        ]  # smoke always baselines against the batched engine (CI gate)
    else:
        # ordered so the claim-critical rejection figures stream first
        # (lambda-grid density per the paper's protocol: rejection ratios
        # are grid-sensitive, see EXPERIMENTS.md)
        suites = [
            ("fig12", paper_tables.fig_rejection_sgl),
            ("fig5", paper_tables.fig5_rejection_dpc),
            ("table3", functools.partial(paper_tables.table3_dpc,
                                         engine=engine)),
            ("table1", functools.partial(paper_tables.table1_sgl_synthetic,
                                         engine=engine)),
            ("table2", functools.partial(paper_tables.table2_adni_scale,
                                         engine=engine)),
            ("engine", paper_tables.engine_bench),
            ("cv", functools.partial(paper_tables.cv_bench, engine=engine,
                                     n_folds=folds)),
            ("cv-pallas", functools.partial(paper_tables.cv_pallas_bench,
                                            n_folds=folds)),
            ("session", functools.partial(paper_tables.session_bench,
                                          n_folds=folds)),
            ("loss-logistic", paper_tables.loss_logistic_bench),
            # LAST: these import repro.analysis, which enables x64
            # process-wide
            ("compile-audit",
             functools.partial(paper_tables.compile_audit_bench,
                               n_folds=min(folds, 3))),
            ("resource-audit",
             functools.partial(paper_tables.resource_audit_bench,
                               n_folds=min(folds, 3))),
            ("feature-shard", paper_tables.feature_shard_bench),
        ]
    only = suite_flag if suite_flag is not None else (argv[0] if argv
                                                     else None)
    print("name,us_per_call,derived", flush=True)
    failures = 0
    for name, fn in suites:
        if only and only not in name:
            continue
        try:
            for row in fn():
                print(f"{row[0]},{row[1]},{row[2]}", flush=True)
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{name},ERROR,failed", flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
