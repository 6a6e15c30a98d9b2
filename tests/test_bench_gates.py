"""The benchmark suite's correctness gates, run on the CPU at smoke size.

``compile-audit`` fails if the engine pays a jit compile key the static
audit did not predict; ``resource-audit`` fails if XLA's peak memory or
FLOPs exceed the static cost-card envelope; ``feature-shard`` fails if
``Plan(feature_shards=8)`` drifts from the single-device engine or its
collective plan is anything but the one partial-fit psum.  They check
invariants, not timings, so they need no TPU (the timed suites of
``benchmarks.run`` refuse to run without one).
"""
import pytest

from benchmarks import paper_tables

GATES = {
    "compile-audit": lambda: paper_tables.compile_audit_bench(n_folds=3),
    "resource-audit": lambda: paper_tables.resource_audit_bench(n_folds=3),
    "feature-shard": paper_tables.feature_shard_bench,
}


@pytest.mark.parametrize("gate", sorted(GATES))
def test_benchmark_gate(gate, monkeypatch):
    """Each gate raises on a broken invariant and otherwise yields rows."""
    monkeypatch.setattr(paper_tables, "SGL_DIMS", dict(N=120, G=60, n=5))
    monkeypatch.setattr(paper_tables, "N_LAMBDA", 16)
    assert list(GATES[gate]())
